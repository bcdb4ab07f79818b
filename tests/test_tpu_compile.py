"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's own compiler runs here, on the CPU, and refuses what
the chip would refuse (tiling, VMEM, partitioning). No test runs anything.

The only file of its kind: one process at a time may load the TPU's
library, so the topology is described inside a fixture of this file, after
a test of it has started — never while a module is imported, and never in
a child process.
"""

import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from ray_tpu.ops import (
    expert_groups,
    expert_step,
    flash_attention,
    gdn_chunk,
    gdn_step,
    latent_attention_chunk,
    latent_attention_decode,
    paged_attention_chunk,
    paged_attention_decode,
    paged_attention_verify,
    pool_shape,
    rms_norm,
)
from ray_tpu.ops.ssd import ssd_chunk, ssd_step

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


# the ops take the pool whole (a token's kv heads in one row) and a layer
LAYERS, LAYER = 2, 1


def _pool(kv_heads=KVH):
    return [(pool_shape(LAYERS, N_PAGES, PAGE, kv_heads, D), BF16)] * 2


_POOL = _pool()


def _paged(batch, *q_shape, heads=(H, KVH)):
    return ([((batch, *q_shape, heads[0], D), BF16)] + _pool(heads[1])
            + [((batch, PAGES_PER_SEQ), I32), ((batch,), I32)])


# heads of 64 (32 query, 8 kv: a pool row of 512 lanes): two kv heads ride
# one 128-lane tile of the same row (ops/paged_attention.py `_tile_heads`)
D64 = 64
_POOL64 = [(pool_shape(LAYERS, N_PAGES, PAGE, KVH, D64), BF16)] * 2


def _paged64(batch, *q_shape):
    return ([((batch, *q_shape, H, D64), BF16)] + _POOL64
            + [((batch, PAGES_PER_SEQ), I32), ((batch,), I32)])


def _at_layer(op):
    return lambda *a: op(*a, layer=LAYER)


# the gated delta rule at its published sizes: 30 heads, a [96, 192] state
# matrix each, 64 slots of 12 layers (ops/gdn.py)
GH, GK, GV, F32 = 30, 96, 192, jnp.float32


def _gdn_operands(*lead):
    return [((*lead, GH, GK), F32), ((*lead, GH, GK), F32),
            ((*lead, GH, GV), F32), ((*lead, GH), F32), ((*lead, GH), F32)]


# ... and with a decay a key channel: 64 heads, a [128, 128] state matrix each
KH, KD = 64, 128


def _kda_operands(*lead):
    return [((*lead, KH, KD), F32), ((*lead, KH, KD), F32),
            ((*lead, KH, KD), F32), ((*lead, KH, KD), F32), ((*lead, KH), F32)]


# the scalar-decay state space at its published sizes: 64 heads of 64, a
# [128, 64] state matrix each, one group, 64 slots of 36 layers (ops/ssd.py)
SH, SP, SN = 64, 64, 128


def _ssd_operands(*lead):
    return [((*lead, SH, SP), F32), ((*lead, SH), F32), ((SH,), F32),
            ((*lead, 1, SN), F32), ((*lead, 1, SN), F32)]


MLA_H, MLA_W, MLA_V = 64, 640, 512
_MLA_POOL = (pool_shape(8, 24577, PAGE, 1, MLA_W), BF16)


def _mla_chunk_past8192(total):
    """A chunk of 256 rows over 8 k cached rows whose tokens end at `total`."""
    return (lambda q, pool, pt: latent_attention_chunk(
                q, pool, pt, 8192, total, 3, MLA_V, 192 ** -0.5),
            [((256, MLA_H, MLA_W), BF16), _MLA_POOL, ((576,), I32)], 1)


def _expert_step(E, D_, F_, act=jax.nn.silu):
    """A decode step's expert product at a cell's shape: 64 rows against
    layer 1 of a segment's stacks of E experts [D_, F_]."""
    return (lambda x, c, hit, w_in, w_gate, w_out: expert_step(
                x, c, hit, w_in, w_gate, w_out, 1, act)[0],
            [((64, D_), BF16), ((64, E), jnp.float32), ((E,), jnp.bool_),
             ((2, E, D_, F_), BF16), ((2, E, D_, F_), BF16),
             ((2, E, F_, D_), BF16)], 1)


def _expert_groups(E, D_, F_, act=jax.nn.silu, rows=256):
    """A prefill chunk's expert product at a cell's shape: each of E experts
    [D_, F_] of layer 1 of a segment's stacks over the rows that chose it,
    of 256 rows that lie whole in VMEM."""
    return (lambda x, c, member, w_in, w_gate, w_out: expert_groups(
                x, c, member, w_in, w_gate, w_out, 1, act),
            [((rows, D_), BF16), ((rows, E), jnp.float32),
             ((rows, E), jnp.bool_), ((2, E, D_, F_), BF16),
             ((2, E, D_, F_), BF16), ((2, E, F_, D_), BF16)], 1)


# name -> (op, [(shape, dtype)], fewest tpu_custom_calls in the program)
CASES = {
    "flash_fwd_t2048": (_flash_fwd, _qkv(2048), 1),
    "flash_fwd_t8192": (_flash_fwd, _qkv(8192), 1),
    "flash_bwd_t2048": (_flash_bwd, _qkv(2048), 2),
    "flash_bwd_t8192": (_flash_bwd, _qkv(8192), 2),
    # one row of 1024 is one block: the same dq block at every step
    "flash_bwd_t1024": (_flash_bwd, _qkv(1024), 2),
    "paged_decode_b8": (_at_layer(paged_attention_decode), _paged(8), 1),
    # the serve cells' batch; ten differential pairs a row under a window
    "paged_decode_b64": (_at_layer(paged_attention_decode), _paged(64), 1),
    "paged_decode_window_10_pairs": (
        lambda *a: paged_attention_decode(*a, layer=LAYER, window=512),
        _paged(64, heads=(40, 10)), 1),
    "paged_decode_one_kv_head": (
        _at_layer(paged_attention_decode), _paged(8, heads=(8, 1)), 1),
    "paged_chunk_c256": (
        lambda q, kp, vp, pt: paged_attention_chunk(
            q, kp, vp, pt, 512, 768, layer=LAYER),
        [((256, H, D), BF16)] + _POOL + [((PAGES_PER_SEQ,), I32)], 1),
    "paged_verify_span4": (_at_layer(paged_attention_verify), _paged(8, 4), 1),
    "paged_verify_span8": (_at_layer(paged_attention_verify), _paged(8, 8), 1),
    "flash_fwd_t256_head64": (
        _flash_fwd, [((1, 256, H, D64), BF16)] + [((1, 256, KVH, D64), BF16)] * 2, 1),
    "paged_decode_b64_head64": (
        _at_layer(paged_attention_decode), _paged64(64), 1),
    "paged_chunk_c256_head64": (
        lambda q, kp, vp, pt: paged_attention_chunk(
            q, kp, vp, pt, 512, 768, layer=LAYER),
        [((256, H, D64), BF16)] + _POOL64 + [((PAGES_PER_SEQ,), I32)], 1),
    "paged_verify_span4_head64": (
        _at_layer(paged_attention_verify), _paged64(8, 4), 1),
    # 30 kv heads of 128 in one row of 3840 lanes, one query head each
    "paged_decode_b64_row3840": (
        _at_layer(paged_attention_decode), _paged(64, heads=(30, 30)), 1),
    "gdn_chunk_t256": (gdn_chunk, _gdn_operands(1, 256)
                       + [((1, GK, GH * GV), F32)], 1),
    "gdn_chunk_t64": (gdn_chunk, _gdn_operands(1, 64)
                      + [((1, GK, GH * GV), F32)], 1),
    "gdn_step_b64": (
        lambda st, *a: gdn_step(st, LAYER, *a),
        [((12, 64, GK, GH * GV), F32)] + _gdn_operands(64)
        + [((64,), jnp.bool_)], 1),
    # the delta rule whose decay is a key channel's, at its published sizes:
    # 64 heads, a [128, 128] state each, 64 slots of 6 layers; a chunk of
    # 256, the wide chunk and a bucket of 64
    "gdn_chunk_channel_t256": (gdn_chunk, _kda_operands(1, 256)
                               + [((1, KD, KH * KD), F32)], 1),
    "gdn_chunk_channel_t512": (gdn_chunk, _kda_operands(1, 512)
                               + [((1, KD, KH * KD), F32)], 1),
    "gdn_chunk_channel_t64": (gdn_chunk, _kda_operands(1, 64)
                              + [((1, KD, KH * KD), F32)], 1),
    "gdn_step_channel_b64": (
        lambda st, *a: gdn_step(st, LAYER, *a),
        [((6, 64, KD, KH * KD), F32)] + _kda_operands(64)
        + [((64,), jnp.bool_)], 1),
    # latent attention at its published sizes: 64 heads over ONE 640-lane row
    # a token (512 of latent, 64 of rotary key, 64 of padding), 8 attentions'
    # rows in one pool, the cell's table of 576 pages; a chunk of 256 over
    # 8 k cached rows; the plain form's heads of 192 against values of 128
    "mla_decode_b64": (
        lambda q, pool, pt, n: latent_attention_decode(
            q, pool, pt, n, 3, MLA_V, 192 ** -0.5),
        [((64, MLA_H, MLA_W), BF16), _MLA_POOL, ((64, 576), I32),
         ((64,), I32)], 1),
    "mla_chunk_c256_past8192": _mla_chunk_past8192(8448),
    # the same chunk holding a question of 96 tokens: 12 tiles of 8 tokens
    # run, 20 loop over no page
    "mla_chunk_c256_past8192_holds96": _mla_chunk_past8192(8288),
    "flash_fwd_t256_keys192_values128": (
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        scale=192 ** -0.5),
        [((4, 256, MLA_H, 192), BF16)] * 2 + [((4, 256, MLA_H, 128), BF16)], 1),
    # window layers whose rings are allocated pages: 7 query heads a kv head,
    # 4 kv heads of 128 in a row of 512 lanes, a ring of 272 pages (a
    # window of 4096 and a chunk of 256) out of the cell's window page
    # space; the chunk reads the ring unrolled over the sequence's 1024 pages
    "paged_decode_window4096_ring272": (
        lambda q, kp, vp, pt, n: paged_attention_decode(
            q, kp, vp, pt, n, 5, window=4096),
        [((64, 28, D), BF16)] + [(pool_shape(9, 8193, PAGE, 4, D), BF16)] * 2
        + [((64, 272), I32), ((64,), I32)], 1),
    "paged_chunk_window4096_past8192": (
        lambda q, kp, vp, pt: paged_attention_chunk(
            q, kp, vp, pt, 8192, 8448, 5, window=4096),
        [((256, 28, D), BF16)] + [(pool_shape(9, 8193, PAGE, 4, D), BF16)] * 2
        + [((1024,), I32)], 1),
    # the expert product of a decode step at the four cells' shapes: blocks
    # of the whole model width by 512, 896, 768 and 256 of the expert width
    "moe_step_8_experts_4096x14336": _expert_step(8, 4096, 14336),
    "moe_step_32_experts_2048x1792": _expert_step(32, 2048, 1792),
    "moe_step_64_experts_2560x768_reglu": _expert_step(
        64, 2560, 768, jax.nn.relu),
    "moe_step_16_experts_6144x2048": _expert_step(16, 6144, 2048),
    # the expert product of a chunk of 256 rows at the same four shapes, a
    # bucket of 64 rows and a chunk of 512 at the narrowest
    "moe_groups_8_experts_4096x14336": _expert_groups(8, 4096, 14336),
    "moe_groups_32_experts_2048x1792": _expert_groups(32, 2048, 1792),
    "moe_groups_64_experts_2560x768_reglu": _expert_groups(
        64, 2560, 768, jax.nn.relu),
    "moe_groups_16_experts_6144x2048": _expert_groups(16, 6144, 2048),
    "moe_groups_64_experts_2560x768_reglu_64_rows": _expert_groups(
        64, 2560, 768, jax.nn.relu, rows=64),
    "moe_groups_64_experts_2560x768_reglu_512_rows": _expert_groups(
        64, 2560, 768, jax.nn.relu, rows=512),
    # the wide chunk's 512 rows (serve/engine.py `_wide_chunk`) at the
    # three other shapes
    "moe_groups_8_experts_4096x14336_512_rows": _expert_groups(
        8, 4096, 14336, rows=512),
    "moe_groups_32_experts_2048x1792_512_rows": _expert_groups(
        32, 2048, 1792, rows=512),
    "moe_groups_16_experts_6144x2048_512_rows": _expert_groups(
        16, 6144, 2048, rows=512),
    # the widest router: 128 experts of 768 (and 130, were the two shared
    # experts two more entries that every row visits): a visit list of up to
    # 128 experts a step, a chunk of 256 and the wide chunk of 512
    "moe_step_128_experts_2048x768": _expert_step(128, 2048, 768),
    "moe_step_130_experts_2048x768": _expert_step(130, 2048, 768),
    "moe_groups_128_experts_2048x768": _expert_groups(128, 2048, 768),
    "moe_groups_128_experts_2048x768_512_rows": _expert_groups(
        128, 2048, 768, rows=512),
    "moe_groups_130_experts_2048x768": _expert_groups(130, 2048, 768),
    # the latent kernels' second shape: 32 query rows a sequence over the
    # same 640-lane row, a table of the agent cell's longest history
    "mla_decode_b64_32_heads": (
        lambda q, pool, pt, n: latent_attention_decode(
            q, pool, pt, n, 3, MLA_V, 192 ** -0.5),
        [((64, 32, MLA_W), BF16), _MLA_POOL, ((64, 768), I32),
         ((64,), I32)], 1),
    "mla_chunk_c256_past8192_32_heads": (
        lambda q, pool, pt: latent_attention_chunk(
            q, pool, pt, 8192, 8448, 3, MLA_V, 192 ** -0.5),
        [((256, 32, MLA_W), BF16), _MLA_POOL, ((768,), I32)], 1),
    # the engine's chunk as ONE block of the dual form, and its buckets
    "ssd_chunk_t256": (ssd_chunk, _ssd_operands(1, 256)
                       + [((1, SN, SH * SP), F32)], 1),
    "ssd_chunk_t128": (ssd_chunk, _ssd_operands(1, 128)
                       + [((1, SN, SH * SP), F32)], 1),
    "ssd_chunk_t64": (ssd_chunk, _ssd_operands(1, 64)
                      + [((1, SN, SH * SP), F32)], 1),
    "ssd_step_b64": (
        lambda st, *a: ssd_step(st, LAYER, *a),
        [((36, 64, SN, SH * SP), F32)] + _ssd_operands(64)
        + [((64,), jnp.bool_)], 1),
    "rms_norm_2048x4096": (
        rms_norm, [((2048, D_MODEL), BF16), ((D_MODEL,), BF16)], 1),
}


# the train cells' attention: (rows, kv heads, window) at 32 heads of 128
# over rows of 8192
_TRAIN_CELLS_ATTENTION = {
    "mistral-7b.train-packed": (1, 8, None),
    "trinity-mini.train-packed-x4 full": (4, 4, None),
    "trinity-mini.train-packed-x4 window": (4, 4, 2048),
}


@pytest.mark.parametrize("cell", sorted(_TRAIN_CELLS_ATTENTION))
def test_the_backward_is_one_kernel_at_the_train_cells_shapes(
        cell, topo, no_persistent_cache):
    """dq, dk and dv of a flash layer come from ONE custom call, named as
    the benchmark counts a backward pass (`flash_bwd_dq`,
    `flash_bwd_window_dq`), which the chip's compiler accepts at 1024 x 1024
    tiles under the `_BWD_VMEM_LIMIT` the call asks for (it refuses them
    under its default); dq leaves it in float32 and whole."""
    rows, kv_heads, window = _TRAIN_CELLS_ATTENTION[cell]
    one_chip = SingleDeviceSharding(topo.devices[0])
    q, k, v = (jax.ShapeDtypeStruct((rows, 8192, h, D), BF16, sharding=one_chip)
               for h in (H, kv_heads, kv_heads))
    text = jax.jit(lambda q, k, v: jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)).lower(q, k, v).compile().as_text()
    calls = re.findall(r"%(flash_\w+?)(?:\.\d+)? = ([^\n]*) custom-call\(", text)
    fwd, bwd = (("flash_fwd", "flash_bwd_dq") if window is None else
                ("flash_fwd_window", "flash_bwd_window_dq"))
    assert sorted(name for name, _ in calls) == sorted([fwd, bwd])
    # dk and dv a query head, and dq
    assert dict(calls)[bwd].count(f"f32[{rows},{H},8192,{D}]") == 3


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
        # the pool shards on its last axis: a shard's row is its kv heads'
        spec(*_POOL[0], None, None, None, None, "tp"),
        spec(*_POOL[0], None, None, None, None, "tp"),
        spec((8, PAGES_PER_SEQ), I32),
        spec((8,), I32),
    ]
    compiled = jax.jit(
        lambda *a: paged_attention_decode(*a, layer=LAYER, mesh=mesh)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The engine's two pool programs at Mistral-7B widths (two layers of them)
# over a pool of 2 x 1 GiB, the engine's default batch, chunk and table.
POOL_PAGES, BATCH, CHUNK = 16385, 64, 256


def _engine_programs(one_chip):
    """-> the pool's shape and {name: () -> lowered} of decode_span_16,
    chunk_prefill_256 and the speculative verify_3, from a bare engine
    object: shapes only, nothing is allocated."""
    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.serve.spec_decode import SpecDecoder

    cfg = get_config("llama3-8b", n_layers=LAYERS, vocab_size=32768,
                     max_seq_len=4096, rope_theta=1e6, dtype="bfloat16")
    ecfg = EngineConfig(max_seq_len=4096, max_batch_size=BATCH,
                        max_pages=POOL_PAGES, prefill_chunk=CHUNK,
                        decode_span=16)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp = cfg, ecfg, None, 1
    spec = object.__new__(SpecDecoder)
    spec.engine, spec.k = eng, 3

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(  # bf16 weights, as a deployment holds them
        lambda a: s(a.shape, BF16),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)))
    pool = eng.abstract_pool(one_chip)  # the engine's own say
    assert pool.shape == pool_shape(LAYERS, POOL_PAGES, PAGE, KVH, D)
    pps = ecfg.pages_per_seq
    f32 = jnp.float32
    return pool, {
        "decode_span_16": lambda: eng._build_decode()(16).lower(
            params, pool, pool, s((BATCH,), I32), s((BATCH,), I32),
            s((BATCH, pps), I32), s((BATCH,), f32), s((BATCH,), f32),
            s((BATCH,), I32), s((2,), jnp.uint32)),
        "chunk_prefill_256": lambda: eng._build_chunk_prefill()(CHUNK).lower(
            params, pool, pool, s((CHUNK,), I32), s((), I32), s((pps,), I32),
            s((), I32), None, s((3,), f32), s((2,), jnp.uint32)),
        # S = k + 1 tokens a slot through the same layers (stack.Verify)
        "verify_3": lambda: spec._build_verify()(False).lower(
            params, pool, pool, s((BATCH, 4), I32), s((BATCH,), I32),
            s((BATCH, pps), I32), s((BATCH,), I32), s((BATCH,), f32),
            s((BATCH,), f32), s((BATCH,), I32), s((2,), jnp.uint32)),
    }


@pytest.mark.parametrize(
    "program", ["decode_span_16", "chunk_prefill_256", "verify_3"])
def test_engine_program_updates_the_pool_in_place(
        program, topo, no_persistent_cache):
    """The compiled program holds ONE pool: the donated inputs are the
    outputs, nothing pool-shaped is copied, and the temporaries are far
    below a pool. The parent of PR 26 (the layer scan took the pool as
    scanned input and stacked output, and the kernels took a layer's slab)
    read here, at these very shapes: 5.10 GiB of temporaries for
    decode_span_16 and 5.02 GiB for chunk_prefill_256 against 2 GiB of
    pool (k and v), with 6 and 2 `copy` operations of the whole pool and 2
    of a layer's slab each. This form reads 0.009 and 0.0006 GiB, and no
    such copy (PERF.md section 6, PR 26)."""
    pool, lowered = _engine_programs(SingleDeviceSharding(topo.devices[0]))
    compiled = lowered[program]().compile()
    memory = compiled.memory_analysis()
    pool_bytes = 2 * pool.size * pool.dtype.itemsize  # k and v
    assert pool_bytes >= 2 ** 30
    assert memory.temp_size_in_bytes < 0.25 * pool_bytes
    # both pools alias the donated arguments 1 and 2 (flattened: after the
    # parameters' leaves), and nothing else is as large
    assert memory.alias_size_in_bytes == pool_bytes
    text = compiled.as_text()
    header = text[: text.index("\n")]
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}, may-alias\)", header)
    assert len(aliased) == 2
    assert "tpu_custom_call" in text
    # no operation copies something pool-shaped (XLA drops the unit axis)
    L, _, pages, ps, row = pool.shape
    assert not re.search(
        r"= bf16\[%d,(1,)?%d,%d,%d\]\S* copy\(" % (L, pages, ps, row), text)


def test_decode_span_under_tp4_updates_its_shard_of_the_pool_in_place(
        topo, no_persistent_cache):
    """The same decode span on a `tp=4` mesh: the mode hands the paged call
    the mesh (models/stack.py: Decode), so the kernel runs per shard on the
    shard's lanes of every row, and each device holds a quarter of the pool,
    donated and handed back, with nothing of that shape copied. Lowered as
    the engine calls it, with the carry of the span before."""
    from ray_tpu.models import get_config, init_params
    from ray_tpu.models.transformer import param_axes
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    mesh = Mesh(topo.devices[:4], ("tp",))
    cfg = get_config("llama3-8b", n_layers=LAYERS, vocab_size=32768,
                     max_seq_len=4096, rope_theta=1e6, dtype="bfloat16")
    ecfg = EngineConfig(max_seq_len=4096, max_batch_size=BATCH,
                        max_pages=POOL_PAGES, prefill_chunk=CHUNK,
                        decode_span=16)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp = cfg, ecfg, mesh, 4

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))

    params = jax.tree.map(
        lambda a, sharding: jax.ShapeDtypeStruct(a.shape, BF16,
                                                 sharding=sharding),
        jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0)),
        tree_shardings(param_axes(cfg), mesh))
    pool = eng.abstract_pool(
        NamedSharding(mesh, P(None, None, None, None, "tp")))
    with mesh:  # as `_under_mesh` runs it; the jitted program is beneath
        compiled = eng._build_decode()(16).__wrapped__.lower(
            params, pool, pool, s((BATCH,), I32), s((BATCH,), I32),
            s((BATCH, ecfg.pages_per_seq), I32), s((BATCH,), jnp.float32),
            s((BATCH,), jnp.float32), s((BATCH,), I32),
            s((2,), jnp.uint32), None,
            (s((BATCH,), I32), s((BATCH,), I32), s((BATCH,), jnp.bool_)),
            n=s((), I32),  # the span's steps: an argument since PR 53
        ).compile()
    # the carry the next span starts from comes out whole on every device,
    # as it went in: the same program again, whatever the partitioner likes
    assert all(out.is_equivalent_to(NamedSharding(mesh, P()), 1)
               for out in compiled.output_shardings[-1])
    memory = compiled.memory_analysis()  # per device
    shard_bytes = 2 * pool.size * pool.dtype.itemsize // 4  # k and v
    assert memory.alias_size_in_bytes == shard_bytes
    assert memory.temp_size_in_bytes < 0.25 * shard_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    L, _, pages, ps, row = pool.shape
    assert not re.search(
        r"= bf16\[%d,(1,)?%d,%d,%d\]\S* copy\(" % (L, pages, ps, row // 4),
        text)


def _train_step_shapes(cfg, one_chip, rows, T):
    """-> (the factored optimizer, a bf16 train state and a batch of rows x
    T as shapes on one described chip): the train cell's recipe."""
    from ray_tpu.models import init_params
    from ray_tpu.train.lm import make_optimizer

    opt = make_optimizer(learning_rate=5e-6, warmup_steps=1, factored=True)

    def state_of(key):
        params = jax.tree.map(lambda a: a.astype(BF16), init_params(cfg, key))
        return {"step": jnp.zeros((), I32), "params": params,
                "opt_state": opt.init(params)}

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(state_of, jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((rows, T), I32, sharding=one_chip)
             for k in ("tokens", "targets")}
    return opt, state, batch


def test_train_step_backward_runs_no_flash_forward(topo, no_persistent_cache):
    """Two layers of the train cell's widths, one row of 8192, bf16, the
    factored optimizer: under `remat` the scan's checkpoint keeps the flash
    kernel's output and log-sum-exp by name (models/transformer.py
    `_remat`), so the compiled step runs the forward kernel ONCE a layer.
    The blanket checkpoint of PR 30's parent read 2 here: one in the
    forward loop's body, one beside `flash_bwd_dq` in the backward's. The
    backward is ONE kernel a layer (`flash_bwd_dq`: dq, dk and dv from one
    pass over the score tiles), where a dq and a dkv kernel stood."""
    from ray_tpu.models import get_config
    from ray_tpu.train.lm import make_train_step

    T = 8192
    cfg = get_config("llama3-8b", n_layers=LAYERS, vocab_size=32768,
                     max_seq_len=T, rope_theta=1e6, dtype="bfloat16")
    assert cfg.remat
    opt, state, batch = _train_step_shapes(
        cfg, SingleDeviceSharding(topo.devices[0]), 1, T)
    text = jax.jit(make_train_step(cfg, opt), donate_argnums=0).lower(
        state, batch).compile().as_text()

    # one computation a `{ ... }` block at column 0; the flash kernels of each
    loops = [kernels for kernels in (
        collections.Counter(re.findall(
            r"%(flash_[a-z_]+)(?:\.\d+)? = [^\n]*tpu_custom_call", block))
        for block in re.split(r"\n}\n", text)) if kernels]
    assert sorted(loops, key=sorted) == [  # two loops, not unrolled
        {"flash_bwd_dq": 1}, {"flash_fwd": 1}]


def test_the_train_cells_step_fits_with_the_gate_kept(
        topo, no_persistent_cache, monkeypatch, tmp_path):
    """`mistral-7b.train-packed` as the benchmark sizes it (8 layers of the
    published widths, 1 x 8192, bf16 masters, the factored optimizer), the
    rule given the chip's limit and the state's bytes in the place of the
    memory this backend does not report: it keeps the FFN's `gate` and not
    `up` (models/transformer.py `kept_under_remat`), the chip's compiler
    accepts the step, the buffer assignment's total (what the chip holds:
    `memory_analysis()` counts kept stacks twice) is under 90% of the
    limit, and the backward's body runs ONE product against `w_gate`'s
    stack where the forward's has its one: 5 -> 4 products of
    [8192, 14336] in the step."""
    from ray_tpu.models import get_config, transformer
    from ray_tpu.train.lm import make_train_step
    from ray_tpu.util import profiler

    T, limit = 8192, 16_909_000_000
    cfg = get_config("llama3-8b", n_layers=8, vocab_size=32768,
                     max_seq_len=T, rope_theta=1e6, dtype="bfloat16")
    opt, state, batch = _train_step_shapes(
        cfg, SingleDeviceSharding(topo.devices[0]), 1, T)
    in_use = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    monkeypatch.setattr(profiler, "device_memory",
                        lambda devices: (limit, in_use))
    compiled = jax.jit(make_train_step(cfg, opt), donate_argnums=0).lower(
        state, batch).compile(compiler_options={
            "xla_dump_to": str(tmp_path), "xla_dump_hlo_as_text": True})
    gate, up = transformer._FFN_NAMES
    stack = 8 * T * cfg.d_ff * 2
    assert profiler._g_remat_kept.get({"name": gate}) == stack
    assert profiler._g_remat_kept.get({"name": up}) == 0
    report = max(tmp_path.glob("*memory-usage-report.txt"),
                 key=lambda f: f.stat().st_size)
    total = int(re.match(r"Total bytes used: (\d+)",
                         report.read_text()).group(1))
    print(f"buffer assignment: {total / 1e9:.3f} GB of {limit / 1e9:.3f}")
    assert 13.9e9 < total < 0.9 * limit
    text = compiled.as_text()
    # the kept stack, written by the forward loop and read by the backward's
    assert "bf16[8,1,8192,14336]" in text
    # gate and up in the forward's body; in the backward's up again and the
    # gradient into the gated product (a fifth, gate again, with today's set)
    assert len(re.findall(
        r"= bf16\[8192,14336\]\S* convolution\(", text)) == 4


def _latent_cell_program(name, program, topo):
    """A latent cell's `program` (`decode_span_8`, `chunk_prefill_<rows>`)
    as the benchmark sizes it, compiled for one described chip through the
    engine's own builders and its own pool (ONE array: there is no pool of
    values). -> (cell, compiled, pool, the program's rows)."""
    from benchmark import common
    from ray_tpu.models import stack
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cell = common.load_cell(name)
    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    ecfg = EngineConfig(**cell["engine"])
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp = cfg, ecfg, None, 1

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda k: family.init_weights(spec, k), jax.random.PRNGKey(0)))
    pool = eng.abstract_pool(one_chip)
    assert pool.shape == (8, 1, 24577, 16, 640)
    B, pps = ecfg.max_batch_size, ecfg.pages_per_seq
    assert eng._wide_chunk() == 2 * ecfg.prefill_chunk == 512
    f32 = jnp.float32
    if program == "decode_span_8":
        rows = B
        lowered = eng._build_decode()(8).lower(
            params, pool, None, s((B,), I32), s((B,), I32), s((B, pps), I32),
            s((B,), f32), s((B,), f32), s((B,), I32), s((2,), jnp.uint32),
            {}, (s((B,), I32), s((B,), I32), s((B,), jnp.bool_)))
    else:
        rows = int(program.rpartition("_")[2])
        rs = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda: stack.new_request_state(cfg, 1, jnp.bfloat16)))
        lowered = eng._build_chunk_prefill()(rows).lower(
            params, pool, None, s((rows,), I32), s((), I32), s((pps,), I32),
            s((), I32), rs, s((3,), f32), s((2,), jnp.uint32))
    compiled = lowered.compile()
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= pool.size * pool.dtype.itemsize
    return cell, compiled, pool, rows


@pytest.mark.parametrize(
    "program", ["decode_span_8", "chunk_prefill_256", "chunk_prefill_512"])
def test_the_latent_cells_programs_hold_one_pool_at_published_widths(
        program, topo, no_persistent_cache):
    """`longcat-flash-omni.serve-docs` as the benchmark sizes it: the double
    layers' program compiles for the chip with ONE pool array (the donated
    latents come back in place, there is no pool of values), two latent
    kernels in the scanned layer's body, no XLA attention, and the memory
    the cell's `pool_filled` quotes: 9.63 GiB of weights + 3.75 GiB of
    latents as arguments, under 0.25 GiB of temporaries. The wide chunk
    (the engine's second chunk program: the model has routed experts) is
    the same two kernels over a grid twice as long."""
    _, compiled, _, _ = _latent_cell_program(
        "longcat-flash-omni.serve-docs", program, topo)
    kernel = "mla_decode" if program == "decode_span_8" else "mla_chunk"
    memory = compiled.memory_analysis()
    gib = 2 ** 30
    assert 13.3 < memory.argument_size_in_bytes / gib < 13.5
    assert memory.temp_size_in_bytes < 0.25 * gib
    text = compiled.as_text()
    assert len(re.findall(r"%%%s(\.\d+)? = " % kernel, text)) == 2
    assert not re.search(r"= bf16\[8,(1,)?24577,16,640\]\S* copy\(", text)


def _names_file_patterns(name, group="shared_experts"):
    """The patterns ONE file under benchmark/trace_names/ adds to a group
    (`trace_reduce.load_names` merges every file into every cell's names,
    so a family's width must be no other cell's)."""
    import json
    import os

    from benchmark import common
    with open(os.path.join(common.HERE, "trace_names", name + ".json")) as f:
        return [re.compile(e["match"]) for e in json.load(f)["groups"][group]]


def _lines_matching(text, patterns):
    """The compiled module's operations that `patterns` name, less those
    that take no time on the device and so never appear in a trace (a
    parameter, a tuple or its element, a bitcast, a constant)."""
    free = re.compile(r" = \(|\b(parameter|get-tuple-element|tuple|bitcast|"
                      r"constant)\(")
    return [line.strip() for line in text.splitlines()
            if any(p.search(line.strip()) for p in patterns)
            and not free.search(line)]



@pytest.mark.parametrize(
    "program", ["decode_span_8", "chunk_prefill_256", "chunk_prefill_512"])
def test_the_agent_cells_programs_run_their_kernels_at_published_widths(
        program, topo, no_persistent_cache):
    """`kanana-2-30b-a3b.serve-agent` as the benchmark sizes it: latent
    attention as one mixer a layer over ONE pool (donated, back in place, no
    pool of values); the leading dense layer once (a latent kernel alone)
    and then ONE scan of seven expert layers whose body holds one latent
    kernel and one expert kernel, the latter taking the segment's three
    expert stacks whole. No XLA attention, no XLA expert product, no copy of
    the pool or of a layer's experts; the shared experts are a dense product
    of width 1536 beside them, which the `shared_experts` names group finds
    and nothing else. Memory is the configuration file's `memory_analysis`:
    9.44 GiB of weights + 3.75 GiB of latents."""
    from benchmark import trace_reduce

    cell, compiled, _, rows = _latent_cell_program(
        "kanana-2-30b-a3b.serve-agent", program, topo)
    step = program == "decode_span_8"
    latent, experts = (("mla_decode", "moe_step") if step
                       else ("mla_chunk", "moe_groups"))
    memory = compiled.memory_analysis()
    gib = 2 ** 30
    print(f"{program}: arguments {memory.argument_size_in_bytes / gib:.3f} "
          f"aliased {memory.alias_size_in_bytes / gib:.3f} "
          f"temporaries {memory.temp_size_in_bytes / gib:.3f} GiB")
    wanted = cell["config"]["memory_analysis"][cell["name"]][
        program.replace("_", " ") + (" x batch 64" if step else "")]
    assert abs(memory.argument_size_in_bytes / gib - wanted["arguments"]) < 0.01
    assert abs(memory.temp_size_in_bytes / gib - wanted["temporaries"]) < 0.02
    text = compiled.as_text()
    # the dense layer's attention and the scanned body's
    assert len(re.findall(r"%%%s(\.\d+)? = " % latent, text)) == 2
    calls = re.findall(r"%%%s(?:\.\d+)? = [^\n]*" % experts, text)
    assert len(calls) == 1
    # the kernel reads the segment's stacks where they lie
    assert calls[0].count("bf16[7,128,2048,768]") == 2
    assert calls[0].count("bf16[7,128,768,2048]") == 1
    assert not re.search(r"= bf16\[8,(1,)?24577,16,640\]\S* copy\(", text)
    assert not re.search(r"= bf16\[(7,)?128,2048,768\]\S* (copy|fusion)\(",
                         text)
    # the shared experts: a product of width 1536 over the program's rows
    assert re.search(r"bf16\[%d,1536\]" % rows, text)
    group = [re.compile(e["match"]) for e in
             trace_reduce.load_names()["groups"]["shared_experts"]]
    named = [line.strip() for line in text.splitlines()
             if any(g.search(line.strip()) for g in group)]
    assert named and all("1536" in line for line in named)
    print("\n".join(line[:200] for line in named))
    # ... and the Solar family's width (1280, merged into these names too)
    # is no operation's of this cell
    assert not _lines_matching(text, _names_file_patterns("solar_open2"))


@pytest.mark.parametrize(
    "program", ["decode_span_8", "chunk_prefill_256", "chunk_prefill_512"])
def test_the_window_and_full_cells_programs_hold_two_page_spaces(
        program, topo, no_persistent_cache):
    """`smallthinker-21b-a3b.serve-mixedlen` as the benchmark sizes it: the
    scanned period of four compiles for the chip with one paged kernel over
    THE pool and three windowed ones over the window page space, both
    spaces' pools donated and handed back in place, no XLA attention, no
    copy of a pool, and the memory the cell's `pool_filled` quotes: 10.36
    GiB of weights + 1.125 GiB of full pages + 2.25 GiB of window pages as
    arguments, under 0.25 GiB of temporaries. The wide chunk (the engine's
    second chunk program: the model has routed experts) calls each layer's
    chunk kernel twice, 256 rows a call, and its experts' kernel once."""
    from benchmark import common
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cell = common.load_cell("smallthinker-21b-a3b.serve-mixedlen")
    spec = cell["config"]
    family = common.family(spec)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp = (
        family.model_config(spec), EngineConfig(**cell["engine"]), None, 1)
    ring = eng._window_ring()  # the window's pages and the wide chunk's
    assert ring == 4096 // 16 + 512 // 16

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda k: family.init_weights(spec, k), jax.random.PRNGKey(0)))
    pool, state = eng.abstract_pool(one_chip), eng.abstract_state(one_chip)
    assert pool.shape == (3, 1, 12289, 16, 512)
    assert {k: v.shape for k, v in state.items()} == {
        "wk": (9, 1, 8193, 16, 512), "wv": (9, 1, 8193, 16, 512)}
    ecfg = eng.ecfg
    B, pps = ecfg.max_batch_size, ecfg.pages_per_seq
    C = int(program.rpartition("_")[2])
    calls = C // ecfg.prefill_chunk or 1  # of a layer's attention kernel
    f32 = jnp.float32
    if program == "decode_span_8":
        lowered = eng._build_decode()(8).lower(
            params, pool, pool, s((B,), I32), s((B,), I32),
            (s((B, pps), I32), s((B, ring), I32)), s((B,), f32), s((B,), f32),
            s((B,), I32), s((2,), jnp.uint32), state,
            (s((B,), I32), s((B,), I32), s((B,), jnp.bool_)))
        kernel = "paged_decode"
    else:
        lowered = eng._build_chunk_prefill()(C).lower(
            params, pool, pool, s((C,), I32), s((), I32),
            (s((pps,), I32), s((ring,), I32)), s((), I32), state,
            s((3,), f32), s((2,), jnp.uint32))
        kernel = "paged_chunk"
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    gib = 2 ** 30
    pools = 2 * pool.size * pool.dtype.itemsize + sum(
        a.size * a.dtype.itemsize for a in state.values())
    assert memory.alias_size_in_bytes >= pools
    assert 13.6 < memory.argument_size_in_bytes / gib < 13.85
    assert memory.temp_size_in_bytes < 0.25 * gib
    text = compiled.as_text()
    assert len(re.findall(r"%%%s(\.\d+)? = " % kernel, text)) == calls
    assert len(re.findall(r"%%%s_window(\.\d+)? = " % kernel, text)) \
        == 3 * calls
    assert not re.search(r"= bf16\[\d+,(1,)?\d+,16,512\]\S* copy\(", text)
    steps = re.findall(r"%moe_step(?:\.\d+)? = [^\n]*", text)
    groups = re.findall(r"%moe_groups(?:\.\d+)? = [^\n]*", text)
    # the experts of a decode step (the experts a live row chose, over all
    # rows) and of a chunk (each expert over the rows that chose it): one
    # kernel a layer of the scanned period, handed the segment's three
    # stacks whole (operands of the loop, not slices of them), and nothing
    # copies or slices ONE layer's experts
    if program.startswith("chunk_prefill"):
        assert not steps
        steps = groups
    else:
        assert not groups
    assert len(steps) == 4
    for call in steps:
        assert len(re.findall(r"bf16\[3,64,2560,768\]\{3,2,1,0\}", call)) == 2
        assert len(re.findall(r"bf16\[3,64,768,2560\]\{3,2,1,0\}", call)) == 1
    assert not re.search(
        r"= bf16\[(1,)?64,(2560,768|768,2560)\]\S* "
        r"(copy|dynamic-slice|slice|fusion)\(", text)


@pytest.mark.parametrize("program", ["decode_span_8", "chunk_prefill_256"])
def test_the_state_space_cells_programs_hold_their_state_in_place(
        program, topo, no_persistent_cache):
    """`granite-4.0-h-micro.serve-chat-burst` as the benchmark sizes it: the
    five runs of Mamba-2 layers scan and the four attention layers stand
    alone; a decode span advances the engine's whole state array
    [36, 64, 128, 4096] float32 (4.5 GiB) in place, one `ssd_step` a scanned
    run and one `paged_decode` an attention layer, and a chunk one
    `ssd_chunk` a run from ONE sequence's state; nothing copies the state
    or the pool. The memory the cell's `pool_filled` quotes: 5.94 GiB of
    weights + 1 GiB of pages + 4.5 GiB of state + 0.06 of tails."""
    from benchmark import common
    from ray_tpu.models import stack
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cell = common.load_cell("granite-4.0-h-micro.serve-chat-burst")
    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp = (
        cfg, EngineConfig(**cell["engine"]), None, 1)
    eng._ring = eng._window_ring()
    assert not eng._ring

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda k: family.init_weights(spec, k), jax.random.PRNGKey(0)))
    pool, state = eng.abstract_pool(one_chip), eng.abstract_state(one_chip)
    assert pool.shape == (4, 1, 8193, 16, 512)
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        "conv": ((36, 64, 3, 4352), BF16),
        "ssd": ((36, 64, 128, 4096), F32)}
    ecfg = eng.ecfg
    B, pps, C = ecfg.max_batch_size, ecfg.pages_per_seq, ecfg.prefill_chunk
    gib = 2 ** 30
    held = sum(a.size * a.dtype.itemsize for a in state.values())
    pools = 2 * pool.size * pool.dtype.itemsize
    if program == "decode_span_8":
        lowered = eng._build_decode()(8).lower(
            params, pool, pool, s((B,), I32), s((B,), I32), s((B, pps), I32),
            s((B,), F32), s((B,), F32), s((B,), I32), s((2,), jnp.uint32),
            state, (s((B,), I32), s((B,), I32), s((B,), jnp.bool_)))
        kernels = {"ssd_step": 5, "paged_decode": 4}
        aliased, arguments = pools + held, (11.4, 11.7)
    else:
        rs = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda: stack.new_request_state(cfg, 1, jnp.bfloat16)))
        lowered = eng._build_chunk_prefill()(C).lower(
            params, pool, pool, s((C,), I32), s((), I32), s((pps,), I32),
            s((), I32), rs, s((3,), F32), s((2,), jnp.uint32))
        kernels = {"ssd_chunk": 5, "paged_chunk": 4}
        aliased, arguments = pools, (6.95, 7.15)
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    print(program, "GiB: arguments %.3f aliased %.3f temporaries %.3f" % (
        memory.argument_size_in_bytes / gib, memory.alias_size_in_bytes / gib,
        memory.temp_size_in_bytes / gib))
    assert memory.alias_size_in_bytes >= aliased
    assert arguments[0] < memory.argument_size_in_bytes / gib < arguments[1]
    assert memory.temp_size_in_bytes < 0.3 * gib
    text = compiled.as_text()
    for kernel, calls in kernels.items():
        assert len(re.findall(r"%%%s(\.\d+)? = " % kernel, text)) == calls
    assert not re.search(r"= f32\[36,64,128,4096\]\S* copy\(", text)
    assert not re.search(r"= bf16\[4,(1,)?8193,16,512\]\S* copy\(", text)


@pytest.mark.parametrize("sampler", ["plain", "sort"])
def test_a_span_to_a_traced_bound_holds_what_the_static_scan_held(
        sampler, topo, no_persistent_cache):
    """A decode program since PR 53 (the span's steps an argument: a loop to
    a traced bound into rows of a [K, B] pair) compiled for a described v5e
    at a tiny hybrid's sizes, state beside its pages, against the static
    scan of K steps it replaced (`static_span`, donating as the engine's
    did), for each sampler: the pool and the state are updated in place in
    both, and the bound costs no temporaries (my compiles, PR 53: none
    against 2.2 MB of the static scan's for the plain sampler's program,
    64.5 MB either way for the sort's), so what fitted beside a pool still
    fits."""
    from test_one_decode_program import static_span

    from ray_tpu.models import get_config, stack
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = get_config("tiny-granite-hybrid", vocab_size=32768)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.mesh, eng._tp = cfg, None, 1
    eng.ecfg = ecfg = EngineConfig(max_batch_size=64, page_size=16,
                                   max_pages=1025, max_seq_len=512)
    eng._ring = eng._window_ring()
    B, pps, K = ecfg.max_batch_size, ecfg.pages_per_seq, ecfg.span_rows
    assert K == 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
        lambda k: stack.init_params(cfg, k), jax.random.PRNGKey(0)))
    pool, state = eng.abstract_pool(one_chip), eng.abstract_state(one_chip)
    assert set(state) == {"conv", "ssd"}
    args = (params, pool, pool, s((B,), I32), s((B,), I32), s((B, pps), I32),
            s((B,), F32), s((B,), F32), s((B,), I32), s((2,), jnp.uint32),
            state)
    program = eng._build_decode()(K, sampler == "sort").lower(
        *args, (s((B,), I32), s((B,), I32), s((B,), jnp.bool_))).compile()
    memory = program.memory_analysis()
    held = 2 * pool.size * pool.dtype.itemsize + sum(
        a.size * a.dtype.itemsize for a in state.values())
    assert memory.alias_size_in_bytes >= held  # tiled: a little more
    text = program.as_text()
    assert " conditional(" not in text
    assert (" sort(" in text) == (sampler == "sort")
    static = jax.jit(static_span(eng, K, sampler).__wrapped__,
                     donate_argnums=(1, 2, 10)).lower(*args).compile()
    scan = static.memory_analysis()
    assert scan.alias_size_in_bytes == memory.alias_size_in_bytes
    print("%s: temporaries, bytes: to a traced bound %d, the static scan %d"
          % (sampler, memory.temp_size_in_bytes, scan.temp_size_in_bytes))
    words = B * cfg.vocab_size * 4  # one [B, vocabulary] buffer
    assert (scan.temp_size_in_bytes > words) == (sampler == "sort")
    assert memory.temp_size_in_bytes <= scan.temp_size_in_bytes + 0.05 * words


# sha256 (12 digits) of each scalar-form delta-rule kernel's Mosaic module,
# printed WITHOUT debug locations, as the parent of PR 52 (b728942) lowers
# it at Olmo's published sizes, at the tests' own matmul precision (`highest`
# marks the kernels' products; at the default setting the three read
# dc311e7a2f83, 6f26eff7a408 and the same 2b7c2315d3dc, on both trees: my
# lowerings, PR 52): the channel form is a second kernel and a
# static branch, and one decay a head must stay the program it was (a
# module's serialized bytes carry line numbers, so the lowered text itself
# moves with any edit above the kernel)
SCALAR_KERNELS = {
    "gdn_chunk_t256": "21c069369683", "gdn_chunk_t64": "c4b799a00503",
    "gdn_step_b64": "2b7c2315d3dc",
}
LOWERED_WITH_JAX = "0.9.0"


@pytest.mark.parametrize("name", sorted(SCALAR_KERNELS))
def test_the_scalar_delta_rule_kernels_are_the_parents(
        name, topo, no_persistent_cache, monkeypatch):
    import hashlib

    import jax._src.tpu_custom_call as tpu_custom_call

    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"digests were taken with jax {LOWERED_WITH_JAX}")
    seen = []
    lower = tpu_custom_call._lower_mosaic_module_to_asm

    def watched(module, **kw):
        seen.append(hashlib.sha256(module.operation.get_asm(
            enable_debug_info=False).encode()).hexdigest()[:12])
        return lower(module, **kw)

    monkeypatch.setattr(tpu_custom_call, "_lower_mosaic_module_to_asm", watched)
    op, specs, _ = CASES[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    # a function of its own: `jax.jit(op)` answers from the lowering another
    # test of this process left behind, and no kernel is lowered again
    jax.jit(lambda *a: op(*a)).lower(*(
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs))
    assert seen == [SCALAR_KERNELS[name]]


@pytest.mark.parametrize("program", ["decode_span_8", "chunk_prefill_256",
                                     "chunk_prefill_512"])
def test_the_channel_decay_cells_programs_hold_state_and_pool_in_place(
        program, topo, no_persistent_cache):
    """`solar-open2-250b.serve-mixedlen` as the benchmark sizes it: ONE scan
    of two periods (gqa kda kda kda); a decode span advances the engine's
    whole state array [6, 64, 128, 8192] float32 (1.5 GiB) in place, one
    `gdn_step` a scanned KDA layer, one `paged_decode` the GQA layer and one
    `moe_step` a layer over the 20 held experts; a chunk one `gdn_chunk` a
    KDA layer from ONE sequence's state and one `moe_groups` a layer (the
    wide chunk attends in two calls of 256 rows); nothing copies the state,
    the pool or the experts. The memory the cell's `pool_filled` quotes:
    7.26 GiB of weights + 1.5 GiB of pages + 1.5 GiB of state + 0.05 of
    tails."""
    from benchmark import common
    from ray_tpu.models import stack
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cell = common.load_cell("solar-open2-250b.serve-mixedlen")
    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.ecfg, eng.mesh, eng._tp = (
        cfg, EngineConfig(**cell["engine"]), None, 1)
    eng._ring = eng._window_ring()
    assert not eng._ring and eng._wide_chunk() == 512

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda k: family.init_weights(spec, k), jax.random.PRNGKey(0)))
    pool, state = eng.abstract_pool(one_chip), eng.abstract_state(one_chip)
    assert pool.shape == (2, 1, 12289, 16, 1024)
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        "conv": ((6, 64, 3, 24576), BF16),
        "gdn": ((6, 64, 128, 8192), F32)}
    ecfg = eng.ecfg
    B, pps = ecfg.max_batch_size, ecfg.pages_per_seq
    gib = 2 ** 30
    held = sum(a.size * a.dtype.itemsize for a in state.values())
    pools = 2 * pool.size * pool.dtype.itemsize
    if program == "decode_span_8":
        lowered = eng._build_decode()(8).lower(
            params, pool, pool, s((B,), I32), s((B,), I32), s((B, pps), I32),
            s((B,), F32), s((B,), F32), s((B,), I32), s((2,), jnp.uint32),
            state, (s((B,), I32), s((B,), I32), s((B,), jnp.bool_)))
        kernels = {"gdn_step": 3, "paged_decode": 1, "moe_step": 4}
        aliased, arguments, temporaries = pools + held, (10.2, 10.45), 0.5
    else:
        C = int(program.rsplit("_", 1)[1])
        rs = jax.tree.map(lambda a: s(a.shape, a.dtype), jax.eval_shape(
            lambda: stack.new_request_state(cfg, 1, jnp.bfloat16)))
        lowered = eng._build_chunk_prefill()(C).lower(
            params, pool, pool, s((C,), I32), s((), I32), s((pps,), I32),
            s((), I32), rs, s((3,), F32), s((2,), jnp.uint32))
        kernels = {"gdn_chunk": 3, "paged_chunk": C // 256, "moe_groups": 4}
        aliased, arguments, temporaries = pools, (8.7, 8.9), 0.25
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    print(program, "GiB: arguments %.3f aliased %.3f temporaries %.3f" % (
        memory.argument_size_in_bytes / gib, memory.alias_size_in_bytes / gib,
        memory.temp_size_in_bytes / gib))
    assert memory.alias_size_in_bytes >= aliased
    assert arguments[0] < memory.argument_size_in_bytes / gib < arguments[1]
    assert memory.temp_size_in_bytes < temporaries * gib
    text = compiled.as_text()
    for kernel, calls in kernels.items():
        assert len(re.findall(r"%%%s(\.\d+)? = " % kernel, text)) == calls
    assert not re.search(r"= f32\[6,64,128,8192\]\S* copy\(", text)
    assert not re.search(r"= bf16\[2,(1,)?12289,16,1024\]\S* copy\(", text)
    assert not re.search(r"= bf16\[(2,)?20,4096,1280\]\S* copy\(", text)
    # the shared expert goes by its width, 1280, which is the routed
    # experts' too: the names match the shared expert's operations (its
    # [2, 4096, 1280] / [2, 1280, 4096] stacks, [rows, 1280] activations),
    # none that touches the 20 held experts' stacks, and the Kanana
    # family's width (1536, merged into these names too) matches nothing
    named = _lines_matching(text, _names_file_patterns("solar_open2"))
    print("\n".join(line[:200] for line in named))
    assert named and not [line for line in named
                          if re.search(r"\[(2,)?20,", line)]
    assert not _lines_matching(text, _names_file_patterns("mla_shared_moe"))


def test_the_channel_decay_cells_weights_are_drawn_a_period_at_a_time(
        topo, no_persistent_cache):
    """The family's `init_weights` at the cell's size: the router's bias is
    balanced on a sample passed through the float32 reference AS the layers
    are drawn, a period a scan iteration, so what is live beside the 7.26
    GiB of weights is one layer's draw and conversion (2.24 GiB) and not
    every layer's slice of the finished tree (4.59 GiB in the first form,
    under which a run of the cell peaked at 15.83 GB of the chip's 16.9:
    chip, PR 52)."""
    from benchmark import common

    cell = common.load_cell("solar-open2-250b.serve-mixedlen")
    spec = cell["config"]
    family = common.family(spec)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=SingleDeviceSharding(topo.devices[0]))
    memory = jax.jit(lambda k: family.init_weights(spec, k)).lower(
        key).compile().memory_analysis()
    gib = 2 ** 30
    print("init_weights GiB: outputs %.3f temporaries %.3f" % (
        memory.output_size_in_bytes / gib, memory.temp_size_in_bytes / gib))
    wanted = spec["memory_analysis"][cell["name"]]["init_weights"]
    assert abs(memory.output_size_in_bytes / gib - wanted["outputs"]) < 0.01
    assert memory.temp_size_in_bytes / gib < wanted["temporaries"] + 0.25


def test_the_trained_stacks_step_fits_and_holds_no_score_matrix(
        topo, no_persistent_cache, tmp_path):
    """`trinity-mini.train-packed-x4` as the benchmark sizes it (5 layers of
    the published widths, 16 of 128 experts, 4 x 8192, bf16 masters, the
    factored optimizer), the program asked for its own shapes: the chip's
    compiler accepts the step with its window flash kernels and grouped
    expert products, the buffer assignment's total is what the
    configuration's file says and under 14.5 GiB, and no program holds a
    [T, T] score matrix (the masked softmax that stood where a window
    binds is gone)."""
    from benchmark import common
    from ray_tpu.train.lm import make_optimizer, make_train_step

    cell = common.load_cell("trinity-mini.train-packed-x4")
    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    opt = make_optimizer(**cell["recipe"])
    one_chip = SingleDeviceSharding(topo.devices[0])

    def state_of(key):
        params = family.init_weights(spec, key)
        return {"step": jnp.zeros((), I32), "params": params,
                "opt_state": opt.init(params)}

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(state_of, jax.random.PRNGKey(0)))
    rows, T = cell["traffic"]["rows_per_step"], cell["traffic"]["row_tokens"]
    batch = {k: jax.ShapeDtypeStruct((rows, T), I32, sharding=one_chip)
             for k in ("tokens", "targets")}
    compiled = jax.jit(make_train_step(cfg, opt), donate_argnums=0).lower(
        state, batch).compile(compiler_options={
            "xla_dump_to": str(tmp_path), "xla_dump_hlo_as_text": True})
    report = max(tmp_path.glob("*memory-usage-report.txt"),
                 key=lambda f: f.stat().st_size).read_text()
    gib = 2 ** 30
    total = int(re.match(r"Total bytes used: (\d+)", report).group(1)) / gib
    wanted = spec["memory_analysis"][cell["name"]]["train_step 4x8192"]
    print(f"buffer assignment: {total:.3f} GiB; the file says {wanted}")
    assert abs(total - wanted["total"]) < 0.3 and total < 14.5
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"%(flash_\w+?|moe_gmm\w*?)(?:\.\d+)? = ", text))
    # a scan's body and a layer alone each hold their kernels once
    assert calls["flash_fwd_window"] == calls["flash_bwd_window_dq"] == 2
    assert calls["flash_fwd"] == calls["flash_bwd_dq"] == 1
    assert not [name for name in calls if name.endswith("_dkv")]  # one pass
    assert calls["moe_gmm_dx"] == calls["moe_gmm_dw"] == 6
    assert not re.search(rf"\[[\d,]*{T},{T}\]", text)  # no [T, T] scores
    # the largest temporaries are the float32 logits and their cotangent
    assert f"f32[{rows},{T},{spec['vocab_size']}]" in text
    # `moe_ffn_device_share.train` finds the experts' XLA operations by this
    # cell's literal shapes (benchmark/trace_names/afmoe.json): each of its
    # patterns still names an operation the step RUNS (not one inside a
    # fusion, which the trace never shows). A change to `grouped_rows_bound`,
    # `grouped_tile` or the rows a step has to re-key that group, or fail here
    run, fused = [], False
    for line in text.splitlines():
        if line.endswith("{"):
            fused = "fused_computation" in line.split("(")[0]
        elif not fused and " = " in line:
            run.append(line.strip().removeprefix("ROOT "))
    names = common.load_json("trace_names", "afmoe.json")["groups"]
    for entry in names["moe_ffn_train"]:
        assert any(re.search(entry["match"], op) for op in run), entry
