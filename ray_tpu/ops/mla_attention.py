"""Latent attention (MLA) over a paged pool of latents.

What a token leaves in the cache is ONE row for all the heads: its latent c
(`value_lanes` wide, after its norm) and the one rotary key the heads share,
side by side, padded with zeros to whole 128-lane tiles. The pool is the
page pool of ops/paged_attention.py with one "kv head" as wide as that row,
[L, 1, num_pages, page_size, W]; there is no pool of values.

Attention runs in the ABSORBED form: a head's query is carried into the
latent's space (q_nope W_k^T, beside its rotary part, the host module's
business), so its score against a cached token is one product with that
token's row, and the weighted sum of the rows' leading `value_lanes` lanes
is carried out again by W_v afterwards. Nothing is up-projected per cached
token and a row is read once, for scores and values both. Every head reads
the same row, so a sequence's H query rows are one block of one product:
2 H (576 + 512) FLOPs for a row's 1280 bytes. Two shapes run it: 64 heads
(109 FLOPs a byte: near a v5e's ridge of 240) and 32 heads (54 a byte:
further under it, so more plainly bound by the rows' bytes), where plain
GQA decode sits at 1 to 8. A change that helps one shape is measured on the
other.

The kernels are `_flash_page_loop` (blocks of page DMAs two deep, the
online softmax, float32 throughout) under two masks:
  mla_decode  one grid program a sequence: its H queries over its pages
  mla_chunk   one grid program a tile of a prefill chunk's tokens: their H
              queries each, tokens x H rows, over the pages up to the
              tile's last token (the prefix and the chunk so far); a tile
              past the chunk's last token, padding alone, over none
XLA references gather the rows (CPU tests and refused shapes).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret_mode, platform_dispatch, slot_order, use_pallas
from .paged_attention import (
    _LANES,
    _NEG_INF,
    _block_pages,
    _flash_page_loop,
    _with_layer,
)

logger = logging.getLogger(__name__)
# rows of queries (tokens x heads) a chunk program holds: its float32
# scores [rows, keys of a block] and accumulator [rows, value_lanes] stay
# in VMEM beside two blocks of pages
_CHUNK_ROWS = 512
_refused = set()


def latent_ok(q, pool, value_lanes: int) -> bool:
    """The kernels' shape gate: rows and values of whole 128-lane tiles,
    query rows of whole sublanes, chunk tiles of whole tokens. A shape it
    refuses is said once (the engine's warm-up traces every program), and
    takes the XLA reference."""
    H, W = q.shape[-2:]
    ok = (W % _LANES == 0 and value_lanes % _LANES == 0 and H % 8 == 0
          and pool.shape[-1] == W and value_lanes <= W
          and (_CHUNK_ROWS % H == 0 or H % _CHUNK_ROWS == 0))
    if use_pallas() and not ok:
        key = (H, W, value_lanes, pool.shape[-1])
        if key not in _refused:
            _refused.add(key)
            logger.warning(
                "latent attention: %d heads over rows of %d lanes (values "
                "%d, pool rows %d) is no shape of the mla_decode / "
                "mla_chunk kernels; the XLA gather reference runs instead",
                *key)
    return use_pallas() and ok


def _rows(pool, layer, page_table):
    """That layer's rows in table order, float32: [..., n * ps, W]."""
    g = pool[layer, 0, page_table]  # [..., n, ps, W]
    return g.reshape(*page_table.shape[:-1], -1, g.shape[-1]).astype(
        jnp.float32)


def _softmax_rows(s, mask, rows, value_lanes, dtype):
    s = jnp.where(mask, s, _NEG_INF)
    p = jnp.where(mask, jax.nn.softmax(s, axis=-1), 0.0)
    return jnp.einsum("...ht,...tv->...hv", p,
                      rows[..., :value_lanes]).astype(dtype)


def _decode_reference(q, pool, page_table, lengths, layer, scale,
                      value_lanes):
    """q [B,H,W] -> [B,H,value_lanes]; a slot of length 0: zeros."""
    rows = _rows(pool, layer, page_table)  # [B, ctx, W]
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32), rows) * scale
    mask = (jnp.arange(rows.shape[1])[None] < lengths[:, None])[:, None]
    return _softmax_rows(s, mask, rows, value_lanes, q.dtype)


def _chunk_reference(q, pool, page_table, start, total, layer, scale,
                     value_lanes):
    """q [C,H,W] -> [C,H,value_lanes]; key j is seen by row c iff
    j <= start + c and j < total."""
    rows = _rows(pool, layer, page_table)  # [ctx, W]
    s = jnp.einsum("chw,tw->cht", q.astype(jnp.float32), rows) * scale
    keypos = jnp.arange(rows.shape[0])[None, :]
    qpos = (start + jnp.arange(q.shape[0]))[:, None]
    mask = ((keypos <= qpos) & (keypos < total))[:, None]
    return _softmax_rows(s, mask, rows[None], value_lanes, q.dtype)


def _scratch(block_rows, W, rows, value_lanes, dtype):
    return [
        pltpu.VMEM((2, block_rows, W), dtype),
        pltpu.VMEM((rows, value_lanes), jnp.float32),
        pltpu.VMEM((rows, _LANES), jnp.float32),
        pltpu.VMEM((rows, _LANES), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 1)),
    ]


def _decode_kernel(pt_ref, meta_ref, q_ref, c_hbm, o_ref,
                   c_buf, acc_ref, m_ref, l_ref, sem_ref,
                   *, page_size, pages_per_seq, scale, batch):
    """One sequence's decode attention: its H queries against its rows. A
    slot of length 0 starts no DMA and writes nothing (its blocks are its
    live neighbour's, as in `_paged_kernel`)."""
    b = pl.program_id(0)
    H, keys = q_ref.shape[1], c_buf.shape[1]
    length = meta_ref[b]
    layer = meta_ref[2 * batch]

    def mask(i):
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (H, keys), 1)
        return pos < length

    @pl.when(length > 0)
    def _live():
        out = _flash_page_loop(
            q_ref[0], jax.lax.div(length + page_size - 1, page_size),
            lambda i: pt_ref[b * pages_per_seq + i], mask, layer, None,
            c_hbm, None, c_buf, None, acc_ref, m_ref, l_ref, sem_ref,
            page_size=page_size, scale=scale)
        o_ref[0] = out.astype(o_ref.dtype)


def _decode_pallas(q, pool, page_table, lengths_layer, scale, value_lanes):
    """lengths_layer s32[B+1]: the B lengths, then the layer index."""
    B, H, W = q.shape
    page_size, pages_per_seq = pool.shape[3], page_table.shape[1]
    # a page is one row's worth, not a key's and a value's: half the width
    block = _block_pages(page_size, W // 2, pool.dtype, H, pages_per_seq)
    lengths = lengths_layer[:B]
    meta = jnp.concatenate(
        [lengths, slot_order(lengths > 0), lengths_layer[B:]])

    def a_slot(width):
        return pl.BlockSpec((1, H, width),
                            lambda b, pt, meta: (meta[B + b], 0, 0))

    out = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size,
                          pages_per_seq=pages_per_seq, scale=scale, batch=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[a_slot(W), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=a_slot(value_lanes),
            scratch_shapes=_scratch(block * page_size, W, H, value_lanes,
                                    pool.dtype)),
        out_shape=jax.ShapeDtypeStruct((B, H, value_lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_decode",
        interpret=interpret_mode(),
    )(page_table.reshape(-1), meta, q, pool)
    return jnp.where((lengths > 0)[:, None, None], out, 0)


def _chunk_kernel(pt_ref, meta_ref, q_ref, c_hbm, o_ref,
                  c_buf, acc_ref, m_ref, l_ref, sem_ref,
                  *, page_size, scale, rows, heads):
    """A tile of a chunk's tokens, row = token * heads + head, against the
    sequence's rows up to the tile's last token (the chunk's own are in
    their pages already). `total` is where the chunk's tokens end: a tile
    past it holds padding alone, loops over no page (no DMA, no product)
    and writes zeros; the padding rows of the tile that holds the last
    token see every key under `total`, so they come out finite."""
    tokens = max(rows // heads, 1)
    keys = c_buf.shape[1]
    start, total, layer = meta_ref[0], meta_ref[1], meta_ref[2]
    first = start + pl.program_id(0) * rows // heads
    seen = jnp.where(first < total, jnp.minimum(first + tokens, total), 0)

    def mask(i):
        keypos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)
        qpos = first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 0) // heads
        return (keypos <= qpos) & (keypos < total)

    out = _flash_page_loop(
        q_ref[...], jax.lax.div(seen + page_size - 1, page_size),
        lambda i: pt_ref[i], mask, layer, None,
        c_hbm, None, c_buf, None, acc_ref, m_ref, l_ref, sem_ref,
        page_size=page_size, scale=scale)
    o_ref[...] = out.astype(o_ref.dtype)


def _chunk_pallas(q, pool, page_table, meta, scale, value_lanes):
    """meta s32[3]: start, total, layer."""
    C, H, W = q.shape
    page_size = pool.shape[3]
    rows = min(C * H, _CHUNK_ROWS)
    block = _block_pages(page_size, W // 2, pool.dtype, rows,
                         page_table.shape[0])
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, page_size=page_size, scale=scale,
                          rows=rows, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(C * H // rows,),
            in_specs=[pl.BlockSpec((rows, W), lambda t, *_: (t, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, value_lanes), lambda t, *_: (t, 0)),
            scratch_shapes=_scratch(block * page_size, W, rows, value_lanes,
                                    pool.dtype)),
        out_shape=jax.ShapeDtypeStruct((C * H, value_lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mla_chunk",
        interpret=interpret_mode(),
    )(page_table, meta, q.reshape(C * H, W), pool)
    return out.reshape(C, H, value_lanes)


def latent_attention_decode(q, pool, page_table, lengths, layer,
                            value_lanes: int, scale: float,
                            force_xla: bool = False):
    """One decode step over the pool of latents.

    q [B, H, W]: every head's absorbed query, laid out as a row of the pool
    is; pool [L, 1, num_pages, page_size, W]; page_table [B, pages_per_seq];
    lengths [B] (0: no sequence, zeros come back); `layer` a scalar.
    -> [B, H, value_lanes]: the softmax-weighted sum of the rows' leading
    `value_lanes` lanes."""
    if force_xla or not latent_ok(q, pool, value_lanes):
        return _decode_reference(q, pool, page_table, lengths, layer, scale,
                                 value_lanes)
    return platform_dispatch(
        lambda *a: _decode_pallas(*a, scale, value_lanes),
        lambda q, pool, pt, m: _decode_reference(
            q, pool, pt, m[:-1], m[-1], scale, value_lanes),
        q, pool, page_table, _with_layer(lengths, layer))


def latent_attention_chunk(q, pool, page_table, start, total, layer,
                           value_lanes: int, scale: float,
                           force_xla: bool = False):
    """ONE sequence's prefill chunk over the pool of latents, the chunk's
    own rows written already: q [C, H, W], page_table [pages_per_seq]; key
    j is seen by query row c iff j <= start + c and j < total. `total` is
    where the chunk's TOKENS end (start < total <= start + C): a row at or
    past it is padding and comes back finite and otherwise unspecified
    (the kernel runs nothing for a whole tile of them and writes zeros).
    -> [C, H, value_lanes]."""
    if force_xla or not latent_ok(q, pool, value_lanes):
        return _chunk_reference(q, pool, page_table, start, total, layer,
                                scale, value_lanes)
    meta = jnp.stack([jnp.asarray(x, jnp.int32)
                      for x in (start, total, layer)])
    return platform_dispatch(
        lambda *a: _chunk_pallas(*a, scale, value_lanes),
        lambda q, pool, pt, m: _chunk_reference(
            q, pool, pt, m[0], m[1], m[2], scale, value_lanes),
        q, pool, page_table, meta)


def write_latent_then_attend(attend, q, row, pool, layer, page_idx,
                             slot_idx):
    """The one place a token's latent row enters the pool (as
    `write_then_attend` is for keys and values): row [*idx, W] to
    ``pool[layer, 0, page_idx, slot_idx]``, then ``attend(q, pool, layer)``
    over the written pool. -> (o, pool)."""
    with jax.named_scope("kv_write"):
        pool = pool.at[(layer, 0, page_idx, slot_idx)].set(
            row.astype(pool.dtype))
    return attend(q, pool, layer), pool
