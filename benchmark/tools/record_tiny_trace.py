#!/usr/bin/env python3
"""Record the small xplane file that benchmark/tests/test_trace_reduce.py
reads: two steps of a one-layer toy train step (flash attention forward and
backward at the published head geometry, a matmul) with an idle pause
between them, on the chip.

    python3 benchmark/tools/record_tiny_trace.py <out.xplane.pb>"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import flash_attention  # noqa: E402


def main(out: str) -> None:
    H, KVH, D, T = 32, 8, 128, 1024
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (1, T, H, D), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, T, KVH, D), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, T, KVH, D), jnp.bfloat16)
    w = jax.random.normal(keys[3], (H * D, H * D), jnp.bfloat16)

    @jax.jit
    def toy_step(q, k, v, w):
        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True).reshape(T, H * D)
            return jnp.sum((o @ w).astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    jax.block_until_ready(toy_step(q, k, v, w))
    logdir = os.path.join(os.path.dirname(os.path.abspath(out)), "_tiny_trace")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    for _ in range(2):
        jax.block_until_ready(toy_step(q, k, v, w))
        time.sleep(0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    shutil.copyfile(found[0], out)
    shutil.rmtree(logdir, ignore_errors=True)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
