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
    """The described chips, with jax's persistent cache off around this
    file's tests: a compile for a described chip is written to it but
    cannot be read back without the chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        described = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
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
def test_the_backward_is_one_kernel_at_the_train_cells_shapes(cell, topo):
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
def test_public_op_compiles_to_a_kernel_on_v5e(name, topo):
    op, specs, min_calls = CASES[name]
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(op).lower(*args).compile()
    # the public op picked its TPU branch from the lowering platform: a
    # shape gate that quietly took the XLA path leaves no custom call
    assert compiled.as_text().count("tpu_custom_call") >= min_calls


def test_paged_decode_compiles_under_tp4_mesh(topo):
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


def _described(cfg, ecfg, topo, weights, mesh=None):
    """-> (an engine of the two configs that holds no array, its programs
    as it describes them itself: `InferenceEngine.programs`) for ONE
    described chip, or for `mesh` over described chips. `weights(key)` makes
    the model's parameters; only their shapes are taken."""
    from ray_tpu.serve.engine import InferenceEngine

    engine = InferenceEngine.abstract(cfg, ecfg, mesh)
    return engine, engine.programs(
        jax.eval_shape(weights, jax.random.PRNGKey(0)),
        SingleDeviceSharding(topo.devices[0]), buckets=())


def _cell_programs(name, topo):
    """A serve cell as the benchmark sizes it -> (the cell, its engine
    without arrays, the engine's programs for one described chip)."""
    from benchmark import common
    from ray_tpu.serve.engine import EngineConfig

    cell = common.load_cell(name)
    spec = cell["config"]
    family = common.family(spec)
    return (cell, *_described(
        family.model_config(spec), EngineConfig(**cell["engine"]), topo,
        lambda key: family.init_weights(spec, key)))


def _bytes(*trees):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(trees))


GIB = 2 ** 30
# the cells whose models route experts run a wide chunk too (`_wide_chunk`)
CELL_PROGRAMS = ["decode_span", "chunk_prefill_256", "chunk_prefill_512"]


def _held_in_place(compiled, aliased, arguments, temporaries, kernels,
                   uncopied):
    """What every cell's test asks of a compiled program: at least `aliased`
    bytes are donated arguments handed back in place, the arguments are
    `arguments` (low, high) GiB and the temporaries under `temporaries`
    GiB, each Pallas kernel of `kernels` is called that many times, and no
    `copy` makes an array of a shape in `uncopied`. -> the program's text."""
    memory = compiled.memory_analysis()
    print("GiB: arguments %.3f aliased %.3f temporaries %.3f" % (
        memory.argument_size_in_bytes / GIB, memory.alias_size_in_bytes / GIB,
        memory.temp_size_in_bytes / GIB))
    assert memory.alias_size_in_bytes >= aliased
    low, high = arguments
    assert low < memory.argument_size_in_bytes / GIB < high
    assert memory.temp_size_in_bytes < temporaries * GIB
    text = compiled.as_text()
    for kernel, calls in kernels.items():
        assert len(re.findall(r"%%%s(\.\d+)? = " % kernel, text)) == calls, kernel
    for shape in uncopied:
        assert not re.search(r"= %s\S* copy\(" % shape, text), shape
    return text


def _mistral_programs(topo, mesh=None):
    """Two layers of Mistral-7B's widths in bf16, as a deployment holds
    them, under the engine's default table and a round of three drafts."""
    from ray_tpu.models import get_config, init_params
    from ray_tpu.serve.engine import EngineConfig

    cfg = get_config("llama3-8b", n_layers=LAYERS, vocab_size=32768,
                     max_seq_len=4096, rope_theta=1e6, dtype="bfloat16")
    speculation = None if mesh else {"mode": "ngram",
                                     "num_speculative_tokens": 3}
    ecfg = EngineConfig(max_seq_len=4096, max_batch_size=BATCH,
                        max_pages=POOL_PAGES, prefill_chunk=CHUNK,
                        decode_span=16, speculation=speculation)
    return _described(
        cfg, ecfg, topo, lambda key: jax.tree.map(
            lambda a: a.astype(BF16), init_params(cfg, key)), mesh)


def _pool_in_place(compiled, pool, shards=1):
    """k and v, a device's share of each, are the donated arguments handed
    back and nothing else is as large; the temporaries are far below them;
    a kernel runs; no operation copies something pool-shaped (XLA drops the
    unit axis). -> the program's text."""
    memory = compiled.memory_analysis()  # per device
    held = _bytes(pool, pool) // shards
    assert memory.alias_size_in_bytes == held
    assert memory.temp_size_in_bytes < 0.25 * held
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    L, _, pages, ps, row = pool.shape
    assert not re.search(r"= bf16\[%d,(1,)?%d,%d,%d\]\S* copy\(" % (
        L, pages, ps, row // shards), text)
    return text


@pytest.mark.parametrize(
    "program", ["decode_span", "chunk_prefill_256", "verify_3"])
def test_engine_program_updates_the_pool_in_place(program, topo):
    """The compiled program holds ONE pool: the donated inputs are the
    outputs, nothing pool-shaped is copied, and the temporaries are far
    below a pool. The parent of PR 26 (the layer scan took the pool as
    scanned input and stacked output, and the kernels took a layer's slab)
    read here, at these very shapes: 5.10 GiB of temporaries for
    the decode span and 5.02 GiB for chunk_prefill_256 against 2 GiB of
    pool (k and v), with 6 and 2 `copy` operations of the whole pool and 2
    of a layer's slab each. This form reads 0.009 and 0.0006 GiB, and no
    such copy (PERF.md section 6, PR 26)."""
    engine, programs = _mistral_programs(topo)
    pool = engine.abstract_pool()  # the engine's own say
    assert pool.shape == pool_shape(LAYERS, POOL_PAGES, PAGE, KVH, D)
    assert _bytes(pool, pool) >= 2 ** 30
    text = _pool_in_place(programs[program].lower().compile(), pool)
    # both pools alias the donated arguments 1 and 2 (flattened: after the
    # parameters' leaves)
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}, may-alias\)",
                         text[: text.index("\n")])
    assert len(aliased) == 2


def test_decode_span_under_tp4_updates_its_shard_of_the_pool_in_place(topo):
    """The same decode span on a `tp=4` mesh: the mode hands the paged call
    the mesh (models/stack.py: Decode), so the kernel runs per shard on the
    shard's lanes of every row, and each device holds a quarter of the pool,
    donated and handed back, with nothing of that shape copied. Lowered as
    the engine calls it, with the carry of the span before."""
    mesh = Mesh(topo.devices[:4], ("tp",))
    engine, programs = _mistral_programs(topo, mesh)
    pool = engine.abstract_pool()
    # as `_under_mesh` runs it; the jitted program is beneath
    compiled = programs["decode_span"].lower().compile()
    # the carry the next span starts from comes out whole on every device,
    # as it went in: the same program again, whatever the partitioner likes
    assert all(out.is_equivalent_to(NamedSharding(mesh, P()), 1)
               for out in compiled.output_shardings[-1])
    _pool_in_place(compiled, pool, shards=4)


def _train_step_shapes(topo, rows, T, cfg=None, weights=None, **recipe):
    """-> (the optimizer of `recipe`, by default the Mistral cell's factored
    one; a train state of `weights(key)`, by default `cfg`'s own in bf16;
    a batch of rows x T), as shapes on one described chip."""
    from ray_tpu.models import init_params
    from ray_tpu.train.lm import make_optimizer

    one_chip = SingleDeviceSharding(topo.devices[0])
    opt = make_optimizer(**(recipe or dict(
        learning_rate=5e-6, warmup_steps=1, factored=True)))
    weights = weights or (lambda key: jax.tree.map(
        lambda a: a.astype(BF16), init_params(cfg, key)))

    def state_of(key):
        params = weights(key)
        return {"step": jnp.zeros((), I32), "params": params,
                "opt_state": opt.init(params)}

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(state_of, jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((rows, T), I32, sharding=one_chip)
             for k in ("tokens", "targets")}
    return opt, state, batch


def test_train_step_backward_runs_no_flash_forward(topo):
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
    opt, state, batch = _train_step_shapes(topo, 1, T, cfg)
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
        topo, monkeypatch, tmp_path):
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
    opt, state, batch = _train_step_shapes(topo, 1, T, cfg)
    in_use = _bytes(state)
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


LATENT_POOL = r"bf16\[8,(1,)?24577,16,640\]"


def _latent_cell_program(name, program, topo):
    """A latent cell's `program` as the benchmark sizes it, compiled for one
    described chip as its engine describes it, over the engine's own pool
    (ONE array: there is no pool of values).
    -> (cell, compiled, the pool's bytes, the program's rows)."""
    cell, engine, programs = _cell_programs(name, topo)
    pool = engine.abstract_pool()
    assert pool.shape == (8, 1, 24577, 16, 640)
    assert engine._wide == 2 * engine.ecfg.prefill_chunk == 512
    rows = (engine.ecfg.max_batch_size if program == "decode_span"
            else int(program.rpartition("_")[2]))
    return cell, programs[program].lower().compile(), _bytes(pool), rows


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_the_latent_cells_programs_hold_one_pool_at_published_widths(
        program, topo):
    """`longcat-flash-omni.serve-docs` as the benchmark sizes it: the double
    layers' program compiles for the chip with ONE pool array (the donated
    latents come back in place, there is no pool of values), two latent
    kernels in the scanned layer's body, no XLA attention, and the memory
    the cell's `pool_filled` quotes: 9.63 GiB of weights + 3.75 GiB of
    latents as arguments, under 0.25 GiB of temporaries. The wide chunk
    (the engine's second chunk program: the model has routed experts) is
    the same two kernels over a grid twice as long."""
    _, compiled, pool_bytes, _ = _latent_cell_program(
        "longcat-flash-omni.serve-docs", program, topo)
    kernel = "mla_decode" if program == "decode_span" else "mla_chunk"
    _held_in_place(compiled, pool_bytes, (13.3, 13.5), 0.25, {kernel: 2},
                   (LATENT_POOL,))


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



@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_the_agent_cells_programs_run_their_kernels_at_published_widths(
        program, topo):
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

    cell, compiled, pool_bytes, rows = _latent_cell_program(
        "kanana-2-30b-a3b.serve-agent", program, topo)
    step = program == "decode_span"
    latent, experts = (("mla_decode", "moe_step") if step
                       else ("mla_chunk", "moe_groups"))
    wanted = cell["config"]["memory_analysis"][cell["name"]][
        "decode span 8 x batch 64" if step else program.replace("_", " ")]
    # the dense layer's attention and the scanned body's; ONE expert call;
    # no copy of the pool or of a layer's experts
    text = _held_in_place(
        compiled, pool_bytes,
        (wanted["arguments"] - 0.01, wanted["arguments"] + 0.01),
        wanted["temporaries"] + 0.02, {latent: 2, experts: 1},
        (LATENT_POOL, r"bf16\[(7,)?128,2048,768\]"))
    assert compiled.memory_analysis().temp_size_in_bytes / GIB \
        > wanted["temporaries"] - 0.02
    (call,) = re.findall(r"%%%s(?:\.\d+)? = [^\n]*" % experts, text)
    # the kernel reads the segment's stacks where they lie
    assert call.count("bf16[7,128,2048,768]") == 2
    assert call.count("bf16[7,128,768,2048]") == 1
    assert not re.search(r"= bf16\[(7,)?128,2048,768\]\S* fusion\(", text)
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


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_the_window_and_full_cells_programs_hold_two_page_spaces(
        program, topo):
    """`smallthinker-21b-a3b.serve-mixedlen` as the benchmark sizes it: the
    scanned period of four compiles for the chip with one paged kernel over
    THE pool and three windowed ones over the window page space, both
    spaces' pools donated and handed back in place, no XLA attention, no
    copy of a pool, and the memory the cell's `pool_filled` quotes: 10.36
    GiB of weights + 1.125 GiB of full pages + 2.25 GiB of window pages as
    arguments, under 0.25 GiB of temporaries. The wide chunk (the engine's
    second chunk program: the model has routed experts) calls each layer's
    chunk kernel twice, 256 rows a call, and its experts' kernel once."""
    _, engine, programs = _cell_programs(
        "smallthinker-21b-a3b.serve-mixedlen", topo)
    # the window's pages and the wide chunk's
    assert engine._ring == 4096 // 16 + 512 // 16
    pool, state = engine.abstract_pool(), engine.abstract_state()
    assert pool.shape == (3, 1, 12289, 16, 512)
    assert {k: v.shape for k, v in state.items()} == {
        "wk": (9, 1, 8193, 16, 512), "wv": (9, 1, 8193, 16, 512)}
    step = program == "decode_span"
    # calls of a layer's attention kernel
    calls = 1 if step else int(
        program.rpartition("_")[2]) // engine.ecfg.prefill_chunk
    kernel = "paged_decode" if step else "paged_chunk"
    text = _held_in_place(
        programs[program].lower().compile(), _bytes(pool, pool, state),
        (13.6, 13.85), 0.25, {kernel: calls, kernel + "_window": 3 * calls},
        (r"bf16\[\d+,(1,)?\d+,16,512\]",))
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


@pytest.mark.parametrize("program", ["decode_span", "chunk_prefill_256"])
def test_the_state_space_cells_programs_hold_their_state_in_place(
        program, topo):
    """`granite-4.0-h-micro.serve-chat-burst` as the benchmark sizes it: the
    five runs of Mamba-2 layers scan and the four attention layers stand
    alone; a decode span advances the engine's whole state array
    [36, 64, 128, 4096] float32 (4.5 GiB) in place, one `ssd_step` a scanned
    run and one `paged_decode` an attention layer, and a chunk one
    `ssd_chunk` a run from ONE sequence's state; nothing copies the state
    or the pool. The memory the cell's `pool_filled` quotes: 5.94 GiB of
    weights + 1 GiB of pages + 4.5 GiB of state + 0.06 of tails."""
    _, engine, programs = _cell_programs(
        "granite-4.0-h-micro.serve-chat-burst", topo)
    assert not engine._ring and not engine._wide
    pool, state = engine.abstract_pool(), engine.abstract_state()
    assert pool.shape == (4, 1, 8193, 16, 512)
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        "conv": ((36, 64, 3, 4352), BF16),
        "ssd": ((36, 64, 128, 4096), F32)}
    held, pools = _bytes(state), _bytes(pool, pool)
    if program == "decode_span":
        kernels = {"ssd_step": 5, "paged_decode": 4}
        aliased, arguments = pools + held, (11.4, 11.7)
    else:
        kernels = {"ssd_chunk": 5, "paged_chunk": 4}
        aliased, arguments = pools, (6.95, 7.15)
    _held_in_place(programs[program].lower().compile(), aliased, arguments,
                   0.3, kernels, (r"f32\[36,64,128,4096\]",
                                  r"bf16\[4,(1,)?8193,16,512\]"))


@pytest.mark.parametrize("sampler", ["plain", "sort"])
def test_a_span_to_a_traced_bound_holds_what_the_static_scan_held(
        sampler, topo):
    """A decode program since PR 53 (the span's steps an argument: a loop to
    a traced bound into rows of a [K, B] pair) compiled for a described v5e
    at a tiny hybrid's sizes, state beside its pages, against the static
    scan of K steps it replaced (`static_span`, donating as the engine's
    did), for each sampler: the pool and the state are updated in place in
    both, and the bound costs no temporaries (my compiles, PR 53, at a
    vocabulary of 32768: none against 2.2 MB of the static scan's for the
    plain sampler's program, 64.5 MB either way for the sort's), so what
    fitted beside a pool still fits. Since PR 56 at a vocabulary of 1024:
    the chip's compiler takes 23 s over ONE sort of [64, 32768] and under
    a second over one of [8, 1024], the case compiled two, and neither the
    bound nor the scan is a property of the vocabulary's width (my
    compiles, PR 56: none against 2.19 and 2.35 MB)."""
    from test_one_decode_program import static_span

    from ray_tpu.models import get_config, stack
    from ray_tpu.serve.engine import EngineConfig

    cfg = get_config("tiny-granite-hybrid", vocab_size=1024)
    ecfg = EngineConfig(max_batch_size=64, page_size=16, max_pages=1025,
                        max_seq_len=512)
    K = ecfg.span_rows
    assert K == 8
    eng, programs = _described(cfg, ecfg, topo,
                               lambda key: stack.init_params(cfg, key))
    pool, state = eng.abstract_pool(), eng.abstract_state()
    assert set(state) == {"conv", "ssd"}
    described = programs["decode_span" + ("_adv" if sampler == "sort" else "")]
    args = described.args[:-1]  # but the carry, which the static scan lacked
    program = described.lower().compile()
    memory = program.memory_analysis()
    held = _bytes(pool, pool, state)
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
    assert memory.temp_size_in_bytes <= scan.temp_size_in_bytes


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
        name, topo, monkeypatch):
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


@pytest.mark.parametrize("program", CELL_PROGRAMS)
def test_the_channel_decay_cells_programs_hold_state_and_pool_in_place(
        program, topo):
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
    _, engine, programs = _cell_programs(
        "solar-open2-250b.serve-mixedlen", topo)
    assert not engine._ring and engine._wide == 512
    pool, state = engine.abstract_pool(), engine.abstract_state()
    assert pool.shape == (2, 1, 12289, 16, 1024)
    assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
        "conv": ((6, 64, 3, 24576), BF16),
        "gdn": ((6, 64, 128, 8192), F32)}
    held, pools = _bytes(state), _bytes(pool, pool)
    if program == "decode_span":
        kernels = {"gdn_step": 3, "paged_decode": 1, "moe_step": 4}
        aliased, arguments, temporaries = pools + held, (10.2, 10.45), 0.5
    else:
        C = int(program.rsplit("_", 1)[1])
        kernels = {"gdn_chunk": 3, "paged_chunk": C // 256, "moe_groups": 4}
        aliased, arguments, temporaries = pools, (8.7, 8.9), 0.25
    text = _held_in_place(
        programs[program].lower().compile(), aliased, arguments, temporaries,
        kernels, (r"f32\[6,64,128,8192\]", r"bf16\[2,(1,)?12289,16,1024\]",
                  r"bf16\[(2,)?20,4096,1280\]"))
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


def test_the_channel_decay_cells_weights_are_drawn_a_period_at_a_time(topo):
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
    print("init_weights GiB: outputs %.3f temporaries %.3f" % (
        memory.output_size_in_bytes / GIB, memory.temp_size_in_bytes / GIB))
    wanted = spec["memory_analysis"][cell["name"]]["init_weights"]
    assert abs(memory.output_size_in_bytes / GIB - wanted["outputs"]) < 0.01
    assert memory.temp_size_in_bytes / GIB < wanted["temporaries"] + 0.25


# the train cells whose model is a stack of unlike layers: the key of the
# step in the configuration's `memory_analysis`, the kernels a compiled step
# holds (a scan's body and a layer alone each hold theirs once), the names'
# file whose shape patterns are this cell's, the other's, and the sorted
# buffer's rows
TRAINED_STACKS = {
    "trinity-mini.train-packed-x4": dict(
        step="train_step 4x8192", names="afmoe.json", other="xing4.json",
        buffer=73728, calls={
            "flash_fwd_window": 2, "flash_bwd_window_dq": 2, "flash_fwd": 1,
            "flash_bwd_dq": 1, "moe_gmm_dx": 6, "moe_gmm_dw": 6,
            "moe_combine": 4}),
    # the dense layer, the scan's body and the prediction block: three of
    # each flash kernel; the body's and the block's experts
    "xing4.0-29b-a4b.train-packed-x2": dict(
        step="train_step 2x8192", names="xing4.json", other="afmoe.json",
        buffer=18432, calls={
            "flash_fwd": 3, "flash_bwd_dq": 3, "moe_gmm_dx": 6,
            "moe_gmm_dw": 6, "moe_combine": 4}),
}


@pytest.mark.parametrize("name", sorted(TRAINED_STACKS))
def test_the_trained_stacks_step_fits_and_holds_no_score_matrix(
        topo, tmp_path, name):
    """A trained stack's cell as the benchmark sizes it (`trinity-mini`: 5
    layers of the published widths, 16 of 128 experts, 4 x 8192;
    `xing4.0-29b-a4b`: 5 latent layers inside four residual streams and the
    prediction block, 8 of 64 experts, 2 x 8192; bf16 masters, the factored
    optimizer), the program asked for its own shapes: the chip's compiler
    accepts the step with its flash kernels and grouped expert products, the
    buffer assignment's total is what the configuration's file says and
    under 14.5 GiB, and no program holds a [T, T] score matrix (the masked
    softmax that stood where a window binds is gone)."""
    from benchmark import common
    from ray_tpu.train.lm import make_train_step

    want = TRAINED_STACKS[name]
    cell = common.load_cell(name)
    spec = cell["config"]
    family = common.family(spec)
    cfg = family.model_config(spec)
    rows, T = cell["traffic"]["rows_per_step"], cell["traffic"]["row_tokens"]
    opt, state, batch = _train_step_shapes(
        topo, rows, T, weights=lambda key: family.init_weights(spec, key),
        **cell["recipe"])
    # at the chip's own matmul precision: the tests' `highest` makes the
    # flash kernels' products float32 passes, whose scratch at heads of 256
    # lanes (24 MB) is past the 16 MB of scoped VMEM; the chip never runs so
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(make_train_step(cfg, opt), donate_argnums=0).lower(
            state, batch).compile(compiler_options={
                "xla_dump_to": str(tmp_path), "xla_dump_hlo_as_text": True})
    report = max(tmp_path.glob("*memory-usage-report.txt"),
                 key=lambda f: f.stat().st_size).read_text()
    total = int(re.match(r"Total bytes used: (\d+)", report).group(1)) / GIB
    wanted = spec["memory_analysis"][cell["name"]][want["step"]]
    print(f"buffer assignment: {total:.3f} GiB; the file says {wanted}")
    assert abs(total - wanted["total"]) < 0.3 and total < 14.5
    text = compiled.as_text()
    calls = collections.Counter(re.findall(
        r"%(flash_\w+?|moe_gmm\w*?|moe_combine)(?:\.\d+)? = ", text))
    assert {k: calls[k] for k in want["calls"]} == want["calls"]
    assert not [k for k in calls if k.endswith("_dkv")]  # one pass
    assert calls["flash_fwd"] + calls["flash_fwd_window"] == sum(
        n for k, n in want["calls"].items() if k.startswith("flash_fwd"))
    # a row leaves the sorted buffer by a gather-sum through the inverse
    # table, forward (the weighted combine) and backward (the gradient of the
    # gather into the buffer): no scatter over the tokens' or the buffer's rows
    # (where the sliced vocabulary has as many rows as the step has tokens,
    # the table's own gradient is a scatter of that shape: the buffer alone)
    over = [want["buffer"]] + [rows * T] * (rows * T != spec["vocab_size"])
    assert not [line for line in text.splitlines() if " scatter(" in line
                and re.search(rf"\[({'|'.join(map(str, over))}),"
                              rf"{spec['hidden_size']}\]", line)]
    assert not re.search(rf"\[[\d,]*{T},{T}\]", text)  # no [T, T] scores
    # the largest temporaries are the float32 logits and their cotangent
    assert f"f32[{rows},{T},{spec['vocab_size']}]" in text
    # the per-layer shares find XLA operations by this cell's literal shapes
    # (benchmark/trace_names/<names>): each pattern still names an operation
    # the step RUNS (not one inside a fusion, which the trace never shows). A
    # change to `grouped_rows_bound`, `grouped_tile`, the rows a step or the
    # streams' layout has to re-key that group, or fail here. And the OTHER
    # trained stack's shape patterns name nothing this step runs: groups
    # merge across the names' files, so one cell's entries must leave the
    # other cell's shares where they were
    run, fused = [], False
    for line in text.splitlines():
        if line.endswith("{"):
            fused = "fused_computation" in line.split("(")[0]
        elif not fused and " = " in line:
            run.append(re.sub(r", metadata=\{.*", "",
                              line.strip().removeprefix("ROOT ")))
    for group, entries in common.load_json(
            "trace_names", want["names"])["groups"].items():
        if group in ("mhc_train", "moe_ffn_train"):
            for entry in entries:
                assert any(re.search(entry["match"], op) for op in run), entry
    for group, entries in common.load_json(
            "trace_names", want["other"])["groups"].items():
        for entry in entries:
            if group in ("mhc_train", "moe_ffn_train") \
                    and "custom-call" not in entry["match"]:
                hit = [op for op in run if re.search(entry["match"], op)]
                assert not hit, (entry, hit[:2])
