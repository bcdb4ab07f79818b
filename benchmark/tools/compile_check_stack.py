#!/usr/bin/env python3
"""compile_check.py for a cell whose model is a stack of unlike layers
(models/stack.py): its engine programs take the per-slot state beside the
page pool, which compile_check.py does not build. Same rehearsal, same
printed line: each program of the cell compiled at its real size for a
described v5e, nothing run.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_check_stack.py <cell>"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark import common  # noqa: E402
from compile_check import report, shaped  # noqa: E402


def stack_cell(cell, one):
    from ray_tpu.models import stack
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    ecfg = EngineConfig(**cell["engine"])
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp, eng._prefill_cache = cfg, ecfg, None, 1, {}
    params = shaped(jax.eval_shape(
        lambda k: family.init_weights(spec, k), jax.random.PRNGKey(0)), one)
    B, pps, ps = ecfg.max_batch_size, ecfg.pages_per_seq, ecfg.page_size
    act, cache = jnp.dtype(cfg.dtype), jnp.dtype(ecfg.cache_dtype)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = s((cfg.count("full"), 1, ecfg.max_pages, ps, cfg.pool_row), cache)
    state = shaped(jax.eval_shape(
        lambda: stack.new_engine_state(cfg, B, ps, act, cache)), one)
    rs = shaped(jax.eval_shape(
        lambda: stack.new_request_state(cfg, 1, act)), one)
    decode = eng._build_decode()
    for span in sorted({ecfg.decode_span, ecfg.busy_span}):
        report(cell["name"], f"decode_span {span} x batch {B}",
               decode(span).lower(
                   params, pool, pool, s((B,), jnp.int32), s((B,), jnp.int32),
                   s((B, pps), jnp.int32), s((B,), jnp.float32),
                   s((B,), jnp.float32), s((B,), jnp.int32),
                   s((2,), jnp.uint32), state))
    C = ecfg.prefill_chunk
    report(cell["name"], f"chunk_prefill {C}",
           eng._build_chunk_prefill()(C).lower(
               params, pool, pool, s((C,), jnp.int32), s((), jnp.int32),
               s((pps,), jnp.int32), s((), jnp.int32), rs))
    for bucket in [b for b in ecfg.prefill_buckets if b <= C]:
        report(cell["name"], f"bucket_prefill {bucket}",
               eng._prefill_fn(bucket, 1).lower(
                   params, s((1, bucket), jnp.int32), s((1,), jnp.int32)))
    install = jax.jit(
        lambda st, r, slot, n: stack.install_state(st, r, slot, n, cfg, ps),
        donate_argnums=0)
    report(cell["name"], "install_state", install.lower(
        state, rs, s((), jnp.int32), s((), jnp.int32)))


def main(names):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for name in names:
        stack_cell(common.load_cell(name), one)


if __name__ == "__main__":
    main(sys.argv[1:])
