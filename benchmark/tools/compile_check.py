#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile each cell's
programs at their real sizes for a v5e that is described, not attached.
Nothing runs; what the chip's compiler would refuse (memory, tiling) is
refused here at no chip time. Prints one JSON line per program with
`memory_analysis()` and the count of `tpu_custom_call`s; the numbers are
copied by hand into the configuration's file (`memory_analysis`).

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_check.py [cell ...]

It reaches into the engine for its jitted programs (a bare engine object:
no pool is allocated), which is what a scratch rehearsal may do and the
benchmark itself never does."""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import common  # noqa: E402

GIB = 2 ** 30


def report(cell, program, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    print(json.dumps({
        "cell": cell, "program": program,
        "argument_gib": round(m.argument_size_in_bytes / GIB, 3),
        "output_gib": round(m.output_size_in_bytes / GIB, 3),
        "alias_gib": round(m.alias_size_in_bytes / GIB, 3),
        "temp_gib": round(m.temp_size_in_bytes / GIB, 3),
        "total_gib": round((m.argument_size_in_bytes + m.output_size_in_bytes
                            - m.alias_size_in_bytes + m.temp_size_in_bytes)
                           / GIB, 3),
        "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        "compile_s": round(time.perf_counter() - t0, 1)}), flush=True)


def shaped(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def train_cell(cell, one):
    from benchmark.train_driver import initial_state
    from ray_tpu.train.lm import make_optimizer, make_train_step

    spec, mix = cell["config"], cell["traffic"]
    cfg = common.family(spec).model_config(spec)
    opt = make_optimizer(**cell["recipe"])
    state = shaped(jax.eval_shape(lambda k: initial_state(spec, opt, k),
                                  jax.random.PRNGKey(0)), one)
    rows, T = mix["rows_per_step"], mix["row_tokens"]
    batch = {k: jax.ShapeDtypeStruct((rows, T), jnp.int32, sharding=one)
             for k in ("tokens", "targets")}
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=0)
    report(cell["name"], f"train_step {rows}x{T}", step.lower(state, batch))


def serve_cell(cell, one):
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    ecfg = EngineConfig(**cell["engine"])
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp, eng._prefill_cache = cfg, ecfg, None, 1, {}
    params = shaped(jax.eval_shape(
        lambda k: family.init_weights(spec, k), jax.random.PRNGKey(0)), one)
    B, pps = ecfg.max_batch_size, ecfg.pages_per_seq
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, cfg.kv_heads, ecfg.max_pages, ecfg.page_size, cfg.hdim),
        jnp.dtype(ecfg.cache_dtype), sharding=one)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    key = s((2,), jnp.uint32)
    decode = eng._build_decode()
    for span in sorted({ecfg.decode_span, ecfg.busy_span}):
        report(cell["name"], f"decode_span {span} x batch {B}",
               decode(span).lower(
                   params, pool, pool, s((B,), jnp.int32), s((B,), jnp.int32),
                   s((B, pps), jnp.int32), s((B,), jnp.float32),
                   s((B,), jnp.float32), s((B,), jnp.int32), key))
    C = ecfg.prefill_chunk
    chunk = eng._build_chunk_prefill()
    report(cell["name"], f"chunk_prefill {C}", chunk(C).lower(
        params, pool, pool, s((C,), jnp.int32), s((), jnp.int32),
        s((pps,), jnp.int32), s((), jnp.int32)))
    for bucket in [b for b in ecfg.prefill_buckets if b <= C]:
        report(cell["name"], f"bucket_prefill {bucket}",
               eng._prefill_fn(bucket, 1).lower(
                   params, s((1, bucket), jnp.int32), s((1,), jnp.int32)))


def main(names):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    manifest = common.load_manifest()
    for name in names or [w["name"] for w in manifest["workloads"]]:
        cell = common.load_cell(name)
        (train_cell if cell["kind"] == "train" else serve_cell)(cell, one)


if __name__ == "__main__":
    main(sys.argv[1:])
