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

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        def lossf(params):
            return loss_fn(params, batch, cfg, forward_fn=forward_fn)

        (_, metrics), grads = jax.value_and_grad(lossf, has_aux=True)(state["params"])
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            new_params = optax.apply_updates(state["params"], updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        metrics["step"] = state["step"]
        return (
            {"step": state["step"] + 1, "params": new_params, "opt_state": new_opt},
            metrics,
        )

    return step


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
