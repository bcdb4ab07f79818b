"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's own compiler runs here, on the CPU, and refuses what
the chip would refuse (tiling, VMEM, partitioning). No test runs anything.

The only file of its kind: one process at a time may load the TPU's
library, so the topology is described inside a fixture of this file, after
a test of it has started — never while a module is imported, and never in
a child process.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ray_tpu.ops import (
    flash_attention,
    paged_attention_chunk,
    paged_attention_decode,
    paged_attention_verify,
    rms_norm,
)

# Llama-3-8B head geometry, the engine's page size, bf16
H, KVH, D, PAGE, D_MODEL = 32, 8, 128, 16, 4096
PAGES_PER_SEQ, N_PAGES = 128, 1032
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, causal=True)


def _flash_bwd(q, k, v):
    return jax.grad(
        lambda q, k, v: jnp.sum(_flash_fwd(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)


def _qkv(t):
    return [((1, t, H, D), BF16), ((1, t, KVH, D), BF16), ((1, t, KVH, D), BF16)]


_POOL = [((KVH, N_PAGES, PAGE, D), BF16)] * 2


def _paged(batch, *q_shape):
    return ([((batch, *q_shape, H, D), BF16)] + _POOL
            + [((batch, PAGES_PER_SEQ), I32), ((batch,), I32)])


# name -> (op, [(shape, dtype)], fewest tpu_custom_calls in the program)
CASES = {
    "flash_fwd_t2048": (_flash_fwd, _qkv(2048), 1),
    "flash_fwd_t8192": (_flash_fwd, _qkv(8192), 1),
    "flash_bwd_t2048": (_flash_bwd, _qkv(2048), 3),
    "flash_bwd_t8192": (_flash_bwd, _qkv(8192), 3),
    "paged_decode_b8": (paged_attention_decode, _paged(8), 1),
    "paged_chunk_c256": (
        lambda q, kp, vp, pt: paged_attention_chunk(q, kp, vp, pt, 512, 768),
        [((256, H, D), BF16)] + _POOL + [((PAGES_PER_SEQ,), I32)], 1),
    "paged_verify_span4": (paged_attention_verify, _paged(8, 4), 1),
    "paged_verify_span8": (paged_attention_verify, _paged(8, 8), 1),
    "rms_norm_2048x4096": (
        rms_norm, [((2048, D_MODEL), BF16), ((D_MODEL,), BF16)], 1),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_public_op_compiles_to_a_kernel_on_v5e(name, topo, no_persistent_cache):
    op, specs, min_calls = CASES[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(op).lower(*args).compile()
    # the public op picked its TPU branch from the lowering platform: a
    # shape gate that quietly took the XLA path leaves no custom call
    assert compiled.as_text().count("tpu_custom_call") >= min_calls


def test_paged_decode_compiles_under_tp4_mesh(topo, no_persistent_cache):
    mesh = Mesh(topo.devices[:4], ("tp",))

    def spec(shape, dtype, *parts):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*parts)))

    args = [
        spec((8, H, D), BF16, None, "tp"),
        spec((KVH, N_PAGES, PAGE, D), BF16, "tp"),
        spec((KVH, N_PAGES, PAGE, D), BF16, "tp"),
        spec((8, PAGES_PER_SEQ), I32),
        spec((8,), I32),
    ]
    compiled = jax.jit(
        lambda *a: paged_attention_decode(*a, mesh=mesh)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
