"""Language-model training step: sharded state init + jittable SPMD step.

This is the TPU-native replacement for what the reference leaves to torch
DDP/FSDP/DeepSpeed inside its Train workers: one train step expressed once,
parallelised entirely by shardings (mesh axes dp/fsdp/tp/sp/ep), with
XLA emitting the ICI collectives.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..models import ModelConfig, init_params, loss_fn, param_axes
from ..parallel.sharding import sharding_for, tree_shardings
from ..util import tracing

TrainState = Dict[str, Any]  # {"step", "params", "opt_state"}


def make_optimizer(
    learning_rate: float = 3e-4,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: Optional[float] = 1.0,
    factored: bool = False,
) -> optax.GradientTransformation:
    """factored=True swaps adamw for adafactor (factored second moments,
    no first moment): optimizer state shrinks from 2x params to ~O(rows +
    cols) — the standard TPU answer for fitting billion-param single-chip
    state (T5's recipe), which `chip_smoke.py`'s train phase takes. NOTE: the
    factored path runs momentum-less and undecayed — b1/b2/weight_decay
    do not apply (adafactor's weight_decay_rate is a per-step
    multiplicative decay, not adamw's lr-scaled decoupled decay)."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1)
    )
    # grad_clip=None/0 drops the clip link entirely. The MPMD pipeline
    # trainer needs this: global-norm clipping must see the WHOLE model's
    # norm, but each stage gang only holds its slice — the trainer sums
    # per-stage sq-norms across gangs and applies the scale itself, so the
    # in-optimizer (per-stage) clip would double-clip with the wrong norm.
    clip = [optax.clip_by_global_norm(grad_clip)] if grad_clip else []
    if factored:
        # Two adafactor traps, both measured fatal on the LM task:
        # - multiply_by_parameter_scale makes updates proportional to
        #   weight norms; with 0.02-scale init that freezes learning at LM
        #   learning rates — scale by the schedule directly instead.
        # - weight_decay_rate is a PER-STEP multiplicative decay (NOT
        #   lr-scaled like adamw's decoupled decay): 0.1 shrinks every
        #   weight 10%/step and cancels all learning. Run undecayed (the
        #   T5 recipe also trains adafactor without decay).
        return optax.chain(
            *clip,
            optax.adafactor(
                schedule, weight_decay_rate=None,
                multiply_by_parameter_scale=False,
            ),
        )
    return optax.chain(
        *clip,
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def _match_shardings_by_shape(shape_tree, params_shardings, params_shapes, mesh):
    """Give optimizer-state leaves the sharding of the same-shaped param.

    optax states (adam mu/nu etc.) mirror param shapes exactly; scalars and
    unmatched leaves replicate. Same-shape params share logical roles (and
    hence shardings) under the default rules, so shape matching is sound.
    """
    by_shape = {}
    for p, s in zip(jax.tree.leaves(params_shapes), jax.tree.leaves(params_shardings)):
        by_shape.setdefault(tuple(p.shape), s)
    replicated = NamedSharding(mesh, PartitionSpec())

    def pick(leaf):
        return by_shape.get(tuple(leaf.shape), replicated)

    return jax.tree.map(pick, shape_tree)


def init_train_state(
    cfg: ModelConfig,
    mesh: Mesh,
    key: jax.Array,
    optimizer: optax.GradientTransformation,
    param_dtype=None,
) -> Tuple[TrainState, Any]:
    """Sharded-from-birth init: params materialize directly into their
    NamedShardings (jit + out_shardings), never resident on one device.

    param_dtype casts the float parameters inside the init jit (bf16
    masters with factored optimizer statistics: the one-chip recipe for
    billion-parameter state), so an f32 tree of the whole model never
    exists on the device. Default: init_params' own f32.

    Returns (state, state_shardings) — pass the latter to jit and to
    checkpoint resharding restore.
    """
    def init_p(key):
        params = init_params(cfg, key)
        if param_dtype is None:
            return params
        return jax.tree.map(lambda x: x.astype(param_dtype), params)

    axes = param_axes(cfg)
    p_shardings = tree_shardings(axes, mesh)
    p_shapes = jax.eval_shape(init_p, key)
    o_shapes = jax.eval_shape(optimizer.init, p_shapes)
    o_shardings = _match_shardings_by_shape(o_shapes, p_shardings, p_shapes, mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    state_shardings = {
        "step": replicated,
        "params": p_shardings,
        "opt_state": o_shardings,
    }

    def _init(key):
        params = init_p(key)
        return {
            "step": jnp.zeros((), jnp.int32),
            "params": params,
            "opt_state": optimizer.init(params),
        }

    with mesh:
        # jit + out_shardings, not eager: leaves materialize directly into
        # their distributed shardings (never whole on one device), and in a
        # multi-process mesh this is the only way to produce global arrays
        state = jax.jit(_init, out_shardings=state_shardings)(key)
    return state, state_shardings


def make_train_step(cfg: ModelConfig, optimizer: optax.GradientTransformation,
                    forward_fn=None):
    """Returns step(state, batch) -> (state, metrics). Jit it under the mesh
    (donate state for in-place HBM update). forward_fn overrides the model
    forward (see make_pp_train_step)."""

    if cfg.is_stack and cfg.untrainable:
        raise NotImplementedError(cfg.untrainable)
    # a stack that holds experts: the step counts every expert layer's
    # choices, for the router's bias and the `train_moe_*` counters
    counts_routes = cfg.is_stack and "moe" in cfg.second_halves

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        def lossf(params):
            return loss_fn(params, batch, cfg, forward_fn=forward_fn,
                           **({"route_counts": True} if counts_routes else {}))

        (_, metrics), grads = jax.value_and_grad(lossf, has_aux=True)(state["params"])
        if counts_routes:
            metrics, counts = metrics
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            new_params = optax.apply_updates(state["params"], updates)
        metrics = dict(metrics)
        if counts_routes:
            new_params, moe = _after_update(cfg, new_params, counts, batch)
            metrics.update(moe)
        metrics["grad_norm"] = optax.global_norm(grads)
        metrics["step"] = state["step"]
        return (
            {"step": state["step"] + 1, "params": new_params, "opt_state": new_opt},
            metrics,
        )

    return step


def _after_update(cfg, params, counts, batch):
    """What a step does with its expert layers' `counts` (int32 [expert
    layers, router outputs]; a multi-token prediction block's experts are
    the last row) once the optimizer has moved the weights: the
    router's bias a step towards an even load (models/stack.py
    `move_router_bias`: the bias has no gradient, and whatever the
    optimizer made of its zeros is overwritten), and the step's numbers,
    each summed over the expert layers: the choices that fell on held
    experts (the rows the grouped products ran over), the fullest held
    expert's rows, the sorted buffers' rows, the biases moved up, and the
    rows the fullest layer's routing needed BEYOND its buffer (0 in a sound
    step; past 0 the layer's output is NaN) -> (params, the numbers as
    metrics). They leave the step in its metrics and nowhere else: the
    device never waits for the host, and a loop that hands what it read to
    `train.report` (or to `profiler.publish_moe_step` itself) adds them to
    the `train_moe_*` counters there, where a step past its bound raises."""
    from ..models import stack
    from ..models.transformer import moe_grouped
    from ..parallel.sharding import _current_mesh

    with jax.named_scope("bias_update"):
        if cfg.router_bias_rate:
            params = stack.move_router_bias(params, counts, cfg)
        first, E = cfg.experts_first, cfg.num_experts
        held = counts[:, first:first + E]
        n = counts.astype(jnp.float32)
        moved_up = jnp.sum(jnp.mean(n, axis=1, keepdims=True) > n) \
            if cfg.router_bias_rate else jnp.zeros((), jnp.int32)
        grouped = moe_grouped(cfg, *batch["tokens"].shape, _current_mesh())
        tile, bound = grouped or (1, 0)
        needed = jnp.max(jnp.sum(
            jnp.maximum(-(-held // tile), 1) * tile, axis=1))
        short = jnp.maximum(needed - bound, 0) if bound \
            else jnp.zeros((), jnp.int32)
    return params, {
        "moe_choices_held": jnp.sum(held),
        "moe_rows_max": jnp.sum(jnp.max(held, axis=1)),
        "moe_rows_bound": jnp.asarray(bound * counts.shape[0]),
        "moe_bias_moved": moved_up, "moe_rows_short": short}


def make_pp_train_step(
    cfg: ModelConfig,
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    num_microbatches: int,
):
    """Pipeline-parallel train step on the real transformer: the layer
    stack runs as a GPipe microbatch pipeline over the mesh's `pp` axis
    (models.transformer.forward_pp), embed/head replicated per stage.
    Same TrainState/shardings as make_train_step — init_train_state on a
    pp mesh already shards the stacked layer axis over pp ("stage" rule,
    parallel/sharding.py). num_microbatches must divide the PER-SHARD
    batch (global batch / dp)."""
    from ..models.transformer import forward_pp

    def fwd(params, tokens, _cfg):
        return forward_pp(params, tokens, _cfg, mesh, num_microbatches)

    return make_train_step(cfg, optimizer, forward_fn=fwd)


def make_eval_step(cfg: ModelConfig):
    def step(params, batch):
        _, metrics = loss_fn(params, batch, cfg)
        return metrics

    return step


def batch_shardings(mesh: Mesh):
    """Input batch layout: batch over data axes, seq over sp."""
    return {
        "tokens": sharding_for(("batch", "seq"), mesh),
        "targets": sharding_for(("batch", "seq"), mesh),
    }


def synthetic_batch(cfg: ModelConfig, batch_size: int, seq_len: int, seed: int = 0):
    """Deterministic fake LM batch (bench / smoke tests / dry runs)."""
    key = jax.random.PRNGKey(seed)
    toks = jax.random.randint(key, (batch_size, seq_len + 1), 0, cfg.vocab_size)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def make_global_batch(batch: Dict[str, Any], shardings: Dict[str, Any]):
    """Assemble global device arrays from host data for a (possibly
    multi-process) mesh: every process passes the same full-size host batch
    and contributes only its addressable shards. In single-process meshes
    this is equivalent to device_put with the sharding."""
    import numpy as np

    def put(x, s):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, s, lambda idx: x[idx])

    with tracing.region("train.place_batch"):
        return jax.tree.map(put, batch, shardings)
