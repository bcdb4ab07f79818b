"""Paged attention for continuous-batching decode.

The serving engine stores KV cache in fixed-size pages in HBM (the vLLM
idea, rebuilt TPU-style): the decode step attends one query token per
sequence against that sequence's pages. The Pallas kernel scalar-prefetches
the page table, then double-buffers page DMAs (HBM→VMEM) behind the MXU
dot products — decode is bandwidth-bound, so overlapping the page fetch is
the whole game. XLA fallback gathers pages (simple, memory-hungry) for CPU
tests and odd shapes.

Cache layout: k_pages / v_pages are [KVH, num_pages, page_size, D] — head
major, so one (head, page) slab is a contiguous [page_size, D] DMA.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import (
    interpret_mode,
    platform_dispatch,
    use_pallas,
)

_NEG_INF = -2.0e30
_LANES = 128


def _paged_reference(q, k_pages, v_pages, page_table, lengths, scale):
    """Gather-based fallback. q [B,H,D] -> o [B,H,D]."""
    B, H, D = q.shape
    KVH, _, page_size, _ = k_pages.shape
    g = H // KVH
    pages_per_seq = page_table.shape[1]
    ctx = pages_per_seq * page_size
    # [KVH, B, pages, ps, D] -> [B, KVH, ctx, D]
    kg = jnp.moveaxis(k_pages[:, page_table], 1, 0).reshape(B, KVH, ctx, D)
    vg = jnp.moveaxis(v_pages[:, page_table], 1, 0).reshape(B, KVH, ctx, D)
    qf = q.reshape(B, KVH, g, D).astype(jnp.float32)
    s = jnp.einsum("bcgd,bctd->bcgt", qf, kg.astype(jnp.float32)) * scale
    mask = jnp.arange(ctx)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bcgt,bctd->bcgd", p, vg.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def _flash_page_loop(
    q2d, n_pages, page_id_fn, mask_fn, c,
    k_hbm, v_hbm, k_buf, v_buf, acc_ref, m_ref, l_ref, sem_ref,
    *, page_size, scale,
):
    """The shared double-buffered page-DMA flash loop: stream this kv
    head's pages HBM->VMEM two-deep while the MXU runs the online-softmax
    update for q2d [rows, D]. Kernels differ only in how a loop index
    maps to a page id (page_id_fn) and in the validity mask
    (mask_fn(i) -> [rows, page_size] bool); everything else — slot
    rotation, the exp-underflow guard, the l==0 epilogue division — is
    one implementation serving both decode and chunk prefill."""

    def page_dma(slot, i):
        page = page_id_fn(i)
        kcp = pltpu.make_async_copy(k_hbm.at[c, page], k_buf.at[slot], sem_ref.at[slot, 0])
        vcp = pltpu.make_async_copy(v_hbm.at[c, page], v_buf.at[slot], sem_ref.at[slot, 1])
        return kcp, vcp

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_pages > 0)
    def _run():
        kcp, vcp = page_dma(0, 0)
        kcp.start()
        vcp.start()

        def body(i, _):
            slot = jax.lax.rem(i, 2)
            nslot = jax.lax.rem(i + 1, 2)

            @pl.when(i + 1 < n_pages)
            def _prefetch():
                kn, vn = page_dma(nslot, i + 1)
                kn.start()
                vn.start()

            kw, vw = page_dma(slot, i)
            kw.wait()
            vw.wait()

            k = k_buf[slot].astype(jnp.float32)  # [ps, D]
            s = jax.lax.dot_general(
                q2d, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, ps]
            s = jnp.where(mask_fn(i), s, _NEG_INF)

            m_prev, l_prev = m_ref[...], l_ref[...]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_next = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next[:, :1])
            p = jnp.where(m_next[:, :1] > _NEG_INF / 2, p, 0.0)
            l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[...] = m_next
            pv = jax.lax.dot_general(
                p, v_buf[slot].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv
            return 0

        jax.lax.fori_loop(0, n_pages, body, 0)

    l = l_ref[...][:, :1]
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc_ref[...] / l)


def _paged_kernel(
    # scalar prefetch
    pt_ref, len_ref,
    # inputs
    q_ref, k_hbm, v_hbm,
    # outputs
    o_ref,
    # scratch
    k_buf, v_buf, acc_ref, m_ref, l_ref, sem_ref,
    *, page_size, pages_per_seq, scale,
):
    b = pl.program_id(0)
    c = pl.program_id(1)
    g = q_ref.shape[2]
    length = len_ref[b]
    n_pages = jax.lax.div(length + page_size - 1, page_size)

    def mask(i):
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (g, page_size), 1)
        return pos < length

    out = _flash_page_loop(
        q_ref[0, 0].astype(jnp.float32), n_pages,
        lambda i: pt_ref[b * pages_per_seq + i], mask, c,
        k_hbm, v_hbm, k_buf, v_buf, acc_ref, m_ref, l_ref, sem_ref,
        page_size=page_size, scale=scale,
    )
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _paged_pallas(q, k_pages, v_pages, page_table, lengths, scale):
    B, H, D = q.shape
    KVH, _, page_size, _ = k_pages.shape
    g = H // KVH
    pages_per_seq = page_table.shape[1]
    q4 = q.reshape(B, KVH, g, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH),
        in_specs=[
            pl.BlockSpec((1, 1, g, D), lambda b, c, *_: (b, c, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, g, D), lambda b, c, *_: (b, c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, D), k_pages.dtype),
            pltpu.VMEM((2, page_size, D), v_pages.dtype),
            pltpu.VMEM((g, D), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, page_size=page_size, pages_per_seq=pages_per_seq, scale=scale
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, g, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="paged_decode",
        interpret=interpret_mode(),
    )(page_table.reshape(-1), lengths, q4, k_pages, v_pages)
    return out.reshape(B, H, D)


def _chunk_reference(q, k_pages, v_pages, page_table, start, total, scale):
    """Gather-based fallback for ONE sequence's prefill chunk.
    q [C,H,D] -> o [C,H,D]; key j visible to query row c iff
    j <= start + c and j < total."""
    C, H, D = q.shape
    KVH, _, page_size, _ = k_pages.shape
    g = H // KVH
    pages_per_seq = page_table.shape[0]
    ctx = pages_per_seq * page_size
    kg = k_pages[:, page_table].reshape(KVH, ctx, D)
    vg = v_pages[:, page_table].reshape(KVH, ctx, D)
    qf = q.reshape(C, KVH, g, D).astype(jnp.float32)
    s = jnp.einsum("ckgd,ktd->ckgt", qf, kg.astype(jnp.float32)) * scale
    keypos = jnp.arange(ctx)
    qpos = start + jnp.arange(C)
    mask = (keypos[None, :] <= qpos[:, None]) & (keypos[None, :] < total)
    s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    o = jnp.einsum("ckgt,ktd->ckgd", p, vg.astype(jnp.float32))
    return o.reshape(C, H, D).astype(q.dtype)


def _chunk_kernel(
    # scalar prefetch
    pt_ref, meta_ref,
    # inputs
    q_ref, k_hbm, v_hbm,
    # outputs
    o_ref,
    # scratch
    k_buf, v_buf, acc_ref, m_ref, l_ref, sem_ref,
    *, page_size, scale, rows, group,
):
    """One kv head's chunk attention: q block [rows=C*g, D] vs the
    sequence's paged prefix (chunk KV already written into pages by the
    caller). The shared _flash_page_loop with a per-ROW causal bound
    instead of the decode kernel's one scalar length."""
    c = pl.program_id(0)
    start = meta_ref[0]
    total = meta_ref[1]
    n_pages = jax.lax.div(total + page_size - 1, page_size)

    def mask(i):
        keypos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // group
        return (keypos <= qpos) & (keypos < total)

    out = _flash_page_loop(
        q_ref[0].astype(jnp.float32), n_pages,
        lambda i: pt_ref[i], mask, c,
        k_hbm, v_hbm, k_buf, v_buf, acc_ref, m_ref, l_ref, sem_ref,
        page_size=page_size, scale=scale,
    )
    o_ref[0] = out.astype(o_ref.dtype)


def _chunk_pallas(q, k_pages, v_pages, page_table, meta, scale):
    C, H, D = q.shape
    KVH, _, page_size, _ = k_pages.shape
    g = H // KVH
    rows = C * g
    # [C,H,D] -> [KVH, C*g, D]: each kv head's q rows contiguous
    qr = q.reshape(C, KVH, g, D).transpose(1, 0, 2, 3).reshape(KVH, rows, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(KVH,),
        in_specs=[
            pl.BlockSpec((1, rows, D), lambda c, *_: (c, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows, D), lambda c, *_: (c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, D), k_pages.dtype),
            pltpu.VMEM((2, page_size, D), v_pages.dtype),
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, page_size=page_size, scale=scale,
            rows=rows, group=g,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KVH, rows, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        name="paged_chunk",
        interpret=interpret_mode(),
    )(page_table, meta, qr, k_pages, v_pages)
    # [KVH, C*g, D] -> [C, H, D]
    return out.reshape(KVH, C, g, D).transpose(1, 0, 2, 3).reshape(C, H, D)


def paged_attention_chunk(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    start,
    total,
    scale: float | None = None,
    force_xla: bool = False,
) -> jax.Array:
    """Chunked-prefill attention for ONE sequence over its paged KV.

    The serving engine writes a prompt chunk's KV into the sequence's
    pages, then calls this with the chunk's queries: key position j is
    visible to query row c iff ``j <= start + c`` (prefix + causal
    intra-chunk) and ``j < total``. Reads only ceil(total/page_size)
    pages — the XLA gather fallback touches the whole table, which is
    the difference at long context.

    Args:
      q: [C, H, D] — the chunk's queries (rope applied).
      k_pages/v_pages: [KVH, num_pages, page_size, D] (chunk KV written).
      page_table: [pages_per_seq] int32 page ids for this sequence.
      start: scalar int — the chunk's first token position.
      total: scalar int — visibility cap (usually start + C).
    Returns [C, H, D].
    """
    C, H, D = q.shape
    KVH = k_pages.shape[0]
    if scale is None:
        scale = D**-0.5
    kernel_ok = use_pallas() and D % _LANES == 0 and H % KVH == 0
    if force_xla or not kernel_ok:
        return _chunk_reference(q, k_pages, v_pages, page_table,
                                start, total, scale)

    def run_pallas(q, kp, vp, pt, meta):
        return _chunk_pallas(q, kp, vp, pt, meta, scale)

    meta = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(total, jnp.int32)])
    return platform_dispatch(
        run_pallas,
        lambda q, kp, vp, pt, _m: _chunk_reference(
            q, kp, vp, pt, start, total, scale),
        q, k_pages, v_pages, page_table, meta,
    )


def _verify_reference(q, k_pages, v_pages, page_table, positions, scale):
    """Gather-based fallback for speculative verify. q [B,S,H,D] ->
    o [B,S,H,D]; key j visible to query (b, s) iff j <= positions[b] + s."""
    B, S, H, D = q.shape
    KVH, _, page_size, _ = k_pages.shape
    g = H // KVH
    pages_per_seq = page_table.shape[1]
    ctx = pages_per_seq * page_size
    # [KVH, B, pages, ps, D] -> [B, KVH, ctx, D]
    kg = jnp.moveaxis(k_pages[:, page_table], 1, 0).reshape(B, KVH, ctx, D)
    vg = jnp.moveaxis(v_pages[:, page_table], 1, 0).reshape(B, KVH, ctx, D)
    qf = q.reshape(B, S, KVH, g, D).astype(jnp.float32)
    s = jnp.einsum("bscgd,bctd->bscgt", qf, kg.astype(jnp.float32)) * scale
    keypos = jnp.arange(ctx)
    qpos = positions[:, None] + jnp.arange(S)[None, :]  # [B, S]
    mask = keypos[None, None, :] <= qpos[:, :, None]  # [B, S, ctx]
    s = jnp.where(mask[:, :, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bscgt,bctd->bscgd", p, vg.astype(jnp.float32))
    return o.reshape(B, S, H, D).astype(q.dtype)


def _verify_kernel(
    # scalar prefetch
    pt_ref, pos_ref,
    # inputs
    q_ref, k_hbm, v_hbm,
    # outputs
    o_ref,
    # scratch
    k_buf, v_buf, acc_ref, m_ref, l_ref, sem_ref,
    *, page_size, pages_per_seq, scale, rows, group, span,
):
    """Speculative-verify attention for one (sequence, kv head): the
    decode kernel generalized from one query token to a span of S=k+1
    (last committed + k draft tokens, KV already written into the
    sequence's pages by the caller). Same double-buffered page streaming;
    the mask becomes the chunk kernel's per-ROW causal bound anchored at
    this sequence's start position."""
    b = pl.program_id(0)
    c = pl.program_id(1)
    start = pos_ref[b]
    total = start + span
    # clamp to THIS sequence's table: a span launched near max_seq_len
    # would otherwise walk into the next sequence's flat table entries
    # (the overflow keys are dead anyway — every row the caller commits
    # has qpos below pages_per_seq * page_size)
    n_pages = jnp.minimum(
        jax.lax.div(total + page_size - 1, page_size), pages_per_seq)

    def mask(i):
        keypos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // group
        return keypos <= qpos

    out = _flash_page_loop(
        q_ref[0, 0].astype(jnp.float32), n_pages,
        lambda i: pt_ref[b * pages_per_seq + i], mask, c,
        k_hbm, v_hbm, k_buf, v_buf, acc_ref, m_ref, l_ref, sem_ref,
        page_size=page_size, scale=scale,
    )
    o_ref[0, 0] = out.astype(o_ref.dtype)


def _verify_pallas(q, k_pages, v_pages, page_table, positions, scale):
    B, S, H, D = q.shape
    KVH, _, page_size, _ = k_pages.shape
    g = H // KVH
    pages_per_seq = page_table.shape[1]
    rows = S * g
    # [B,S,H,D] -> [B, KVH, S*g, D]: each kv head's q rows contiguous,
    # row = s*g + gi so row // g recovers the span offset (mask anchor)
    qr = (q.reshape(B, S, KVH, g, D)
          .transpose(0, 2, 1, 3, 4).reshape(B, KVH, rows, D))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH),
        in_specs=[
            pl.BlockSpec((1, 1, rows, D), lambda b, c, *_: (b, c, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, D), lambda b, c, *_: (b, c, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, page_size, D), k_pages.dtype),
            pltpu.VMEM((2, page_size, D), v_pages.dtype),
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _verify_kernel, page_size=page_size, pages_per_seq=pages_per_seq,
            scale=scale, rows=rows, group=g, span=S,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, rows, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="paged_verify",
        interpret=interpret_mode(),
    )(page_table.reshape(-1), positions, qr, k_pages, v_pages)
    # [B, KVH, S*g, D] -> [B, S, H, D]
    return (out.reshape(B, KVH, S, g, D)
            .transpose(0, 2, 1, 3, 4).reshape(B, S, H, D))


def paged_attention_verify(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    positions: jax.Array,
    scale: float | None = None,
    force_xla: bool = False,
    mesh=None,
    tp_axis: str = "tp",
) -> jax.Array:
    """Speculative-decode verify attention over the paged KV cache.

    The engine writes the span's KV (last committed token + k draft
    tokens, at positions p..p+k) into each sequence's pages, then scores
    all S=k+1 positions in ONE forward: key j is visible to query row s
    of sequence b iff ``j <= positions[b] + s`` (committed prefix +
    causal within the speculative window). S=1 degenerates to exactly
    paged_attention_decode's semantics.

    Args:
      q: [B, S, H, D] — span queries per sequence (rope applied).
      k_pages/v_pages: [KVH, num_pages, page_size, D] (span KV written).
      page_table: [B, pages_per_seq] int32 page ids.
      positions: [B] int32 — position of each sequence's row 0 (== its
        committed length; rows past a shorter draft are masked by the
        caller's accept logic, not here).
      mesh/tp_axis: tensor-parallel serving, same shard_map wrap as
        paged_attention_decode (q heads + page-pool KVH dim sharded).
    Returns [B, S, H, D].
    """
    D = q.shape[-1]
    KVH = k_pages.shape[0]
    if scale is None:
        scale = D**-0.5

    def dispatch(q, kp, vp, pt, pos):
        return platform_dispatch(
            lambda *a: _verify_pallas(*a, scale),
            lambda *a: _verify_reference(*a, scale),
            q, kp, vp, pt, pos,
        )

    tp = int(mesh.shape.get(tp_axis, 1)) if mesh is not None else 1
    kernel_ok = (
        use_pallas()
        and D % _LANES == 0
        and q.shape[2] % KVH == 0
        and (tp == 1 or KVH % tp == 0)
    )
    if force_xla or not kernel_ok:
        return _verify_reference(q, k_pages, v_pages, page_table,
                                 positions, scale)
    if tp > 1:
        from jax.sharding import PartitionSpec as P

        return jax.shard_map(
            dispatch,
            mesh=mesh,
            in_specs=(
                P(None, None, tp_axis, None),  # q: heads sharded
                P(tp_axis), P(tp_axis),        # page pools: KVH sharded
                P(), P(),                      # table/positions replicated
            ),
            out_specs=P(None, None, tp_axis, None),
            check_vma=False,
        )(q, k_pages, v_pages, page_table, positions)
    return dispatch(q, k_pages, v_pages, page_table, positions)


def paged_attention_decode(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    scale: float | None = None,
    force_xla: bool = False,
    mesh=None,
    tp_axis: str = "tp",
) -> jax.Array:
    """One decode step of attention over a paged KV cache.

    Args:
      q: [B, H, D] — current token's query per sequence.
      k_pages/v_pages: [KVH, num_pages, page_size, D].
      page_table: [B, pages_per_seq] int32 page ids (unused tail arbitrary).
      lengths: [B] int32 valid context length per sequence.
      force_xla: skip the Pallas kernel entirely (tests/debug).
      mesh/tp_axis: tensor-parallel serving. A bare pallas_call cannot be
        partitioned by GSPMD, so under tp>1 the kernel is wrapped in
        shard_map over the tp axis: each shard runs the same kernel on its
        contiguous block of q heads and kv heads (page pool sharded on the
        KVH dim — requires tp | KVH, which the engine enforces). The
        page_table/lengths scalars replicate.
    Returns [B, H, D].
    """
    D = q.shape[-1]
    KVH = k_pages.shape[0]
    if scale is None:
        scale = D**-0.5

    def dispatch(q, kp, vp, pt, ln):
        return platform_dispatch(
            lambda *a: _paged_pallas(*a, scale),
            lambda *a: _paged_reference(*a, scale),
            q, kp, vp, pt, ln,
        )

    tp = int(mesh.shape.get(tp_axis, 1)) if mesh is not None else 1
    # tp | KVH is the only TP constraint: H = g*KVH makes H % tp == 0 follow
    kernel_ok = (
        use_pallas()
        and D % _LANES == 0
        and q.shape[1] % KVH == 0
        and (tp == 1 or KVH % tp == 0)
    )
    if force_xla or not kernel_ok:
        return _paged_reference(q, k_pages, v_pages, page_table, lengths, scale)
    if tp > 1:
        from jax.sharding import PartitionSpec as P

        return jax.shard_map(
            dispatch,
            mesh=mesh,
            in_specs=(
                P(None, tp_axis, None),        # q: heads sharded
                P(tp_axis), P(tp_axis),        # page pools: KVH sharded
                P(), P(),                      # table/lengths replicated
            ),
            # no collectives in the body; pallas_call outputs don't carry
            # vma annotations, so the varying-axes checker can't see through
            out_specs=P(None, tp_axis, None),
            check_vma=False,
        )(q, k_pages, v_pages, page_table, lengths)
    return dispatch(q, k_pages, v_pages, page_table, lengths)
