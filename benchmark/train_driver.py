"""Cells of kind `train`: the `train-packed` job through `JaxTrainer.fit()`
with the gang member in this process, a `ray_tpu.data` Dataset feeding it.

The loop inside the window is the one a user writes: dispatch the step,
fetch the next batch while the device runs, read the loss every
`read_loss_every` steps; one `block_until_ready`, at the window's end."""

from __future__ import annotations

import math
import os
import time
from typing import Any, Dict

from . import checks, common, traffic, weights

# set by run(), read by train_loop: the gang member runs in this process, and
# the trainer pickles train_loop's config, which cannot carry device arrays
_SHARED: Dict[str, Any] = {}


def _state_shardings(cfg, mesh, opt, p_shapes):
    """Where each leaf of the train state lives: parameters by the
    program's own rules, optimizer statistics like the parameter of the
    same shape, scalars replicated (as train/lm.py::init_train_state)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ray_tpu.models import param_axes
    from ray_tpu.parallel.sharding import tree_shardings

    p_shardings = tree_shardings(param_axes(cfg), mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    by_shape = {}
    for p, s in zip(jax.tree.leaves(p_shapes), jax.tree.leaves(p_shardings)):
        by_shape.setdefault(tuple(p.shape), s)
    o_shapes = jax.eval_shape(opt.init, p_shapes)
    o_shardings = jax.tree.map(
        lambda leaf: by_shape.get(tuple(leaf.shape), replicated), o_shapes)
    return {"step": replicated, "params": p_shardings,
            "opt_state": o_shardings}


def initial_state(spec: Dict[str, Any], opt, key):
    """The train state of the seed, from the benchmark's own weights."""
    import jax.numpy as jnp

    params = common.family(spec).init_weights(spec, key)
    return {"step": jnp.zeros((), jnp.int32), "params": params,
            "opt_state": opt.init(params)}


def build_step(cell: Dict[str, Any], cfg, mesh, seed: int):
    """-> (state, jitted step, batch shardings). The state is born sharded,
    from the benchmark's own seeded weights."""
    import jax

    from ray_tpu.train.lm import batch_shardings, make_optimizer, make_train_step

    spec = cell["config"]
    opt = make_optimizer(**cell["recipe"])
    key = weights.seed_key(seed)
    p_shapes = jax.eval_shape(
        lambda k: common.family(spec).init_weights(spec, k), key)
    shardings = _state_shardings(cfg, mesh, opt, p_shapes)
    with mesh:
        state = jax.jit(lambda k: initial_state(spec, opt, k),
                        out_shardings=shardings)(key)
        step = jax.jit(make_train_step(cfg, opt), donate_argnums=0,
                       out_shardings=(shardings, shardings["step"]))
    return state, step, batch_shardings(mesh)


def train_loop(config: Dict[str, Any]) -> None:
    """The gang member. Reports one row: what the window measured."""
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.comm.mesh import MeshSpec, build_mesh
    from ray_tpu.train.lm import make_global_batch

    cell, seconds = _SHARED["cell"], config["seconds"]
    watch = _SHARED["watch"]
    cfg = common.family(cell["config"]).model_config(cell["config"])
    mesh_axes = cell["mesh_axes"]
    n_dev = math.prod(mesh_axes.values())
    mesh = build_mesh(MeshSpec.create(**mesh_axes),
                      devices=jax.devices()[:n_dev])
    state, step, shardings = build_step(cell, cfg, mesh, config["seed"])
    rows_per_step = cell["traffic"]["rows_per_step"] * n_dev
    row_tokens = cell["traffic"]["row_tokens"]

    def batches():
        shard = train.get_dataset_shard("train")
        while True:  # the corpus repeats if the window outlasts it
            for batch in shard.iter_batches(
                    batch_size=rows_per_step, drop_last=True,
                    prefetch_batches=cell["prefetch_batches"]):
                toks = np.stack([np.asarray(t) for t in batch["tokens"]])
                yield toks

    def put(toks):
        return make_global_batch(
            {"tokens": toks[:, :-1], "targets": toks[:, 1:]}, shardings)

    it = batches()
    first = next(it)
    with mesh:
        # warm-up: the one shape this cell uses, on the first batch, whose
        # loss the plain reference checks after the window
        state, metrics = step(state, put(first))
        first_metrics = {k: float(v) for k, v in metrics.items()}
        batch = put(next(it))
        jax.block_until_ready((state, batch))
        compiles_before = watch.snapshot()
        trace = _SHARED.get("trace")
        losses, input_wait, steps = [], 0.0, 0
        traced = {}
        t0 = time.perf_counter()
        _SHARED["t_window"] = t0
        while time.perf_counter() - t0 < seconds:
            if trace and steps == trace.start_step:
                jax.block_until_ready(state)
                trace.start()
                traced["t0"], traced["step0"] = time.perf_counter(), steps
            state, metrics = step(state, batch)
            steps += 1
            w0 = time.perf_counter()
            batch = put(next(it))
            input_wait += time.perf_counter() - w0
            if steps % cell["read_loss_every"] == 0:
                losses.append(float(metrics["loss"]))
            if traced and "t1" not in traced and (
                    time.perf_counter() - traced["t0"] >= trace.seconds):
                jax.block_until_ready(state)
                traced["t1"], traced["step1"] = time.perf_counter(), steps
                trace.stop()
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
        if traced and "t1" not in traced:
            traced["t1"], traced["step1"] = time.perf_counter(), steps
            trace.stop()
        losses.append(float(metrics["loss"]))
    compiles = watch.snapshot()["compiles"] - compiles_before["compiles"]
    train.report({
        "steps": steps, "tokens": steps * rows_per_step * row_tokens,
        "window_s": window_s, "input_wait_s": input_wait, "losses": losses,
        "compiles_in_window": compiles, "first_metrics": first_metrics,
        "first_batch": first,
        "traced_steps": (traced["step1"] - traced["step0"]) if traced else 0,
        "traced_s": (traced["t1"] - traced["t0"]) if traced else 0.0,
    })
    del state, batch


def run(cell: Dict[str, Any], args, device: Dict[str, Any], watch,
        t_start: float, tracer) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    mix, spec = cell["traffic"], cell["config"]
    rows = traffic.packed_rows(
        mix, args.seed, cell["corpus_rows"], spec["vocab_size"])
    _SHARED.update(cell=cell, watch=watch, trace=tracer)
    ray_tpu.init()
    try:
        ds = rt_data.from_items([{"tokens": row} for row in rows])
        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"seed": args.seed, "seconds": args.seconds},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         mesh_shape=cell["mesh_axes"]),
            run_config=RunConfig(
                name=cell["name"],
                storage_path=os.path.join(common.ROOT, ".bench_runs")),
            datasets={"train": ds},
        )
        result = trainer.fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise common.BenchFailure(f"training failed: {result.error!r}") \
            from result.error
    out = result.metrics_history[-1]
    setup_s = _SHARED["t_window"] - t_start
    peak = common.memory_peak_bytes(cell["chips"])
    common.wait_for_free_memory(cell["chips"])
    finite = all(math.isfinite(x) for x in out["losses"])
    common.say(check="losses_finite", losses=out["losses"], ok=finite)
    no_compiles = out["compiles_in_window"] == 0
    common.say(check="compiles_in_window", value=out["compiles_in_window"],
               limit=0, ok=no_compiles)
    ok = checks.train(cell, args.seed, out["first_batch"], out["first_metrics"])
    tokens_per_s = out["tokens"] / out["window_s"]
    return {
        "correct": finite and no_compiles and ok,
        "attempted": out["steps"], "failed": 0,
        "end_to_end": {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        "memory_peak_bytes": peak,
        "run": {**{k: v for k, v in out.items() if k != "first_batch"},
                "tokens_per_s": tokens_per_s,
                "tokens_per_step": out["tokens"] / max(out["steps"], 1)},
    }
