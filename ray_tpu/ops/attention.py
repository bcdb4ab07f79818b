"""Flash attention for TPU: Pallas forward kernel + blockwise XLA fallback.

Replaces what the reference reaches CUDA flash-attn for (via the torch /
vLLM stacks it orchestrates — upstream ray has no attention kernel of its
own). Design follows the TPU memory hierarchy:

- Forward is a Pallas kernel gridded (batch, heads, q-blocks, kv-blocks)
  with the kv-block axis innermost ("arbitrary") so Mosaic double-buffers
  HBM->VMEM tile fetches behind the MXU matmuls. Online-softmax stats live
  in VMEM scratch that persists across the kv axis.
- GQA is handled with index maps (kv head = q head // group), so K/V are
  never materialized at full head count — saves G× HBM traffic vs repeat.
- Backward on the TPU path is ONE fused Pallas kernel gridded (batch,
  heads, kv-blocks, q-blocks) that reads the forward's logsumexp residual
  and makes one pass over the score tiles: a tile's s, p, dp and ds are
  computed once and feed dv, dk (VMEM scratch across a key block's steps)
  and dq (a float32 array in HBM whose blocks the kernel reads, adds to
  and writes back with its own DMAs): 5 products a tile. GQA dk/dv are
  computed per-q-head and group-summed outside the kernel. Off-TPU
  platforms fall back to a `lax.scan` XLA formulation with identical
  semantics.

Layout convention: public API is [B, T, H, D] (model layout); kernels run
[B, H, T, D].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret_mode, platform_dispatch, use_pallas
from .paged_attention import _tile_heads, _untile_heads, tile_factor

_NEG_INF = -2.0e30
_LANES = 128
_MAX_BLOCK = 1024  # measured knee on v5e: 1024² blocks ~3.4x faster than 128²
# The backward kernel holds four [block_q, block_k] f32 tiles (s, p, dp, ds)
# beside its double-buffered operands: over 16.46 MiB at 1024² blocks, which
# the chip's compiler refuses under its default 16 MiB scoped-VMEM limit
# (seen at T=8192 compiled for a described v5e). A v5e has 128 MiB of VMEM.
_BWD_VMEM_LIMIT = 32 * 1024 * 1024
# What `_flash_fwd_rule` names its output and log-sum-exp, for a
# `jax.checkpoint` policy that saves by name (models/transformer.py).
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _auto_block(t: int) -> int:
    """Largest power-of-two block <= _MAX_BLOCK dividing t (>=128 floor).

    Bigger tiles amortize Mosaic per-program overhead and keep the MXU fed;
    measured on v5e (B8 S2048 H12 D128): fwd 9.3->3.8ms, fwd+bwd
    18.3->5.4ms going from 128^2 to 1024^2 blocks."""
    b = _MAX_BLOCK
    while b > 128 and t % b:
        b //= 2
    return b


def mha_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """O(T²) reference attention, [B, T, H, D]; used for tests only.
    `window`: a query at position i sees the keys i - window < j <= i."""
    B, Tq, H, D = q.shape
    Tk, KVH = k.shape[1], k.shape[2]
    if scale is None:
        scale = D**-0.5
    g = H // KVH
    qh = q.reshape(B, Tq, KVH, g, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qh, k, preferred_element_type=jnp.float32)
    s = s * scale
    if causal:
        q_pos = q_offset + jnp.arange(Tq)
        mask = q_pos[:, None] >= jnp.arange(Tk)[None, :]
        if window is not None:
            mask &= jnp.arange(Tk)[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(B, Tq, H, D)


# ---------------------------------------------------------------------------
# A window: query i sees the keys i - window < j <= i. The window kernels
# are the causal ones over a NARROWER grid: a query block's innermost axis
# runs over the key blocks its window reaches and no others (`_key_span`),
# a key block's over the query blocks that reach it (`_query_span`); the
# index maps name those blocks alone, so a block wholly outside the window
# is never loaded. The diagonal and the window's edge blocks are masked.
# ---------------------------------------------------------------------------


def _key_span(window, block_q, block_k, nq):
    """How many key blocks a query block's window reaches at the most."""
    return max((i * block_q + block_q - 1) // block_k
               - max(i * block_q - window + 1, 0) // block_k + 1
               for i in range(nq))


def _query_span(window, block_q, block_k, nq, nk):
    """How many query blocks reach a key block at the most."""
    return max(min((j * block_k + block_k + window - 2) // block_q, nq - 1)
               - (j * block_k) // block_q + 1 for j in range(nk))


def _key_block(i, jj, span, block_q, block_k):
    """The key block that step jj of `span` is for query block i: the
    last one is the diagonal's; negative before the sequence's start."""
    return (i * block_q + block_q - 1) // block_k - (span - 1) + jj


def _query_block(j, ii, block_q, block_k):
    """The query block that step ii is for key block j: the first one is
    the diagonal's; past the sequence's end it names no block."""
    return (j * block_k) // block_q + ii


def _window_mask(i, j, block_q, block_k, window):
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return (q_pos >= k_pos) & (k_pos > q_pos - window)


def _window_runs(i, j, block_q, block_k, window, nq=None):
    """Whether blocks (i, j) hold a pair inside the window: j a key block
    at or after the sequence's start, i a query block before its end (`nq`
    blocks; None: the grid names no block past it)."""
    runs = ((j >= 0) & (i * block_q + block_q - 1 >= j * block_k)
            & (j * block_k + block_k - 1 > i * block_q - window))
    return runs if nq is None else runs & (i < nq)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, *rest, scale, causal, block_q, block_k, return_lse,
    window=None,
):
    if return_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref, (acc_ref, m_ref, l_ref) = None, rest
    i, j = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Blocks strictly above the diagonal contribute nothing under causal
    # masking: skip the MXU work (the tile fetch still happens — acceptable;
    # a bespoke index_map could skip it too).
    if window is not None:
        # the grid's j is a step of the window's span: jb is its key block
        jb = _key_block(i, j, nk, block_q, block_k)
        run = _window_runs(i, jb, block_q, block_k, window)
    elif causal:
        run = i * block_q + block_q - 1 >= j * block_k
    else:
        run = jnp.bool_(True)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * scale  # [bq, bk]
        if window is not None:
            s = jnp.where(_window_mask(i, jb, block_q, block_k, window), s, _NEG_INF)
        elif causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_ref[...]  # [bq, LANES] (row-replicated)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)  # [bq, LANES]
        alpha = jnp.exp(m_prev - m_next)  # [bq, LANES]
        # s is [bq, block_k]; m_next row-replicated so any LANES-slice works.
        p = jnp.exp(s - m_next[:, :1])  # [bq, bk]
        # Rows where everything (incl. running max) is masked: kill them.
        p = jnp.where(m_next[:, :1] > _NEG_INF / 2, p, 0.0)
        l_next = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_next
        l_ref[...] = l_next
        pv = jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, D]
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + pv

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp residual for the backward, lane-replicated
            lse_ref[0, 0] = m_ref[...] + jnp.log(jnp.where(l_ref[...] == 0.0, 1.0, l_ref[...]))


def _flash_fwd_pallas(q, k, v, *, causal, scale, block_q, block_k, return_lse=False,
                      window=None):
    """q [B,H,T,D], k/v [B,KVH,T,D] -> o [B,H,T,D] (and lse [B,H,T] f32)."""
    B, H, Tq, D = q.shape
    KVH, Tk = k.shape[1], k.shape[2]
    g = H // KVH
    grid = (B, H, Tq // block_q, Tk // block_k)
    kv_at = lambda b, h, i, j: (b, h // g, j, 0)
    extra = {}
    if window is not None:
        span = _key_span(window, block_q, block_k, grid[2])
        grid = (*grid[:3], span)
        kv_at = lambda b, h, i, j: (b, h // g, jnp.maximum(
            _key_block(i, j, span, block_q, block_k), 0), 0)
        extra = dict(window=window)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        return_lse=return_lse, **extra,
    )
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    out_specs = [pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))]
    if return_lse:
        # lane-replicated [B,H,Tq,LANES]; sliced to [B,H,Tq] after the call
        out_shape.append(jax.ShapeDtypeStruct((B, H, Tq, _LANES), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, 1, block_q, _LANES), lambda b, h, i, j: (b, h, i, 0))
        )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), kv_at),
            pl.BlockSpec((1, 1, block_k, D), kv_at),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * B * H * Tq * Tk * D * (0.5 if causal else 1.0)),
            bytes_accessed=int((q.size + k.size + v.size + q.size) * q.dtype.itemsize),
            transcendentals=int(B * H * Tq * Tk),
        ) if window is None else pl.CostEstimate(
            flops=int(4 * B * H * Tq * min(window, Tk) * D),
            bytes_accessed=int((q.size + k.size + v.size + q.size) * q.dtype.itemsize),
            transcendentals=int(B * H * Tq * min(window, Tk)),
        ),
        name="flash_fwd" if window is None else "flash_fwd_window",
        interpret=interpret_mode(),
    )(q, k, v)
    if return_lse:
        o, lse_rep = out
        return o, lse_rep[..., 0]
    return out[0]


# ---------------------------------------------------------------------------
# Pallas backward kernel: ONE pass over the score tiles, kv-major. A tile's
# s, mask, p, dp and ds are computed once and feed all three gradients:
# dv += p^T do and dk += ds^T q into VMEM scratch that lives across a key
# block's query steps, dq += ds k into a float32 array in HBM that the
# kernel reads, adds to and writes back with its own DMAs.
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, lse_ref, delta_ref, do_ref, dk_ref, dv_ref, dq_hbm,
    dk_acc, dv_acc, dq_old, dq_new, sems, in_flight,
    *, scale, causal, block_q, block_k, window=None, nq=None,
):
    b, h = pl.program_id(0), pl.program_id(1)
    j, i = pl.program_id(2), pl.program_id(3)  # kv-major: q blocks innermost
    nj, ni = pl.num_programs(2), pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((i == 0) & (j == 0))
    def _no_write_yet():
        in_flight[0] = -1

    # ib: the step's query block; `first`: the first key block that reaches
    # it, whose step writes dq's block without reading it (key blocks come
    # in ascending order, so no other step has written it)
    if window is not None:
        # the grid's i is a step of the span of query blocks that reach
        # key block j
        ib = _query_block(j, i, block_q, block_k)
        run = _window_runs(ib, j, block_q, block_k, window, nq)
        first = jnp.maximum(ib * block_q - window + 1, 0) // block_k
    else:
        ib, first = i, 0
        run = i * block_q + block_q - 1 >= j * block_k if causal else jnp.bool_(True)

    def dq_block(block):
        return dq_hbm.at[b, h, pl.ds(pl.multiple_of(block * block_q, block_q), block_q)]

    def write(block=0):  # a wait needs the copy's shape alone
        return pltpu.make_async_copy(dq_new, dq_block(block), sems.at[1])

    @pl.when(run)
    def _compute():
        read = pltpu.make_async_copy(dq_block(ib), dq_old, sems.at[0])

        # in_flight: the dq block whose write-back may still be under way
        # (-1: none). One of THIS block lands before the block is read.
        @pl.when(in_flight[0] == ib)
        def _land():
            write().wait()
            in_flight[0] = -1

        @pl.when(j != first)
        def _fetch():
            read.start()

        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        if window is not None:
            s = jnp.where(_window_mask(ib, j, block_q, block_k, window), s, _NEG_INF)
        elif causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])  # masked entries -> exp(-inf)=0
        # contract over the q axis (axis 0 of both): p^T @ do without an
        # explicit transpose — the MXU takes it as a dot_general directly.
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0][:, :1]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        # the step before's write-back had four products' time to land
        @pl.when(in_flight[0] >= 0)
        def _free():
            write().wait()

        dq_new[...] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        @pl.when(j != first)
        def _add():
            read.wait()
            dq_new[...] += dq_old[...]

        write(ib).start()
        in_flight[0] = ib

    @pl.when(i == ni - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    # a (batch, head)'s last write lands before its programs end: the next
    # one's start from no write in flight, whichever core runs them
    @pl.when((i == ni - 1) & (j == nj - 1) & (in_flight[0] >= 0))
    def _drain():
        write().wait()


def _flash_bwd_pallas(q, k, v, o, lse, do, *, causal, scale, block_q, block_k,
                      dlse=None, window=None):
    """Fused backward: q/o/do [B,H,Tq,D], k/v [B,KVH,Tk,D], lse [B,H,Tq] f32.

    Returns (dq, dk, dv) in the input dtypes. dk/dv are computed per q-head
    inside the kernel and summed over the GQA group outside (an [B,H,Tk,D]
    f32 transient — XLA fuses the group-sum with the cast); dq leaves the
    kernel in float32, the sum over key blocks it is. An lse cotangent
    (ring attention) folds in as a delta shift: d lse_i/d s_ij = p_ij."""
    B, H, Tq, D = q.shape
    KVH, Tk = k.shape[1], k.shape[2]
    g = H // KVH
    nq, nk = Tq // block_q, Tk // block_k

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    lse_rep = jnp.broadcast_to(lse[..., None], (B, H, Tq, _LANES))
    delta_rep = jnp.broadcast_to(delta[..., None], (B, H, Tq, _LANES))

    # kv-major grid (b, h, j, i). A step that does not run names the block
    # of the next one that does (the diagonal's under the causal mask, the
    # sequence's last past its end), which the pipeline does not fetch again
    steps, extra = nq, {}
    pairs = 0.5 if causal else 1.0  # of Tq x Tk, what the cost estimate counts
    if window is not None:
        steps = _query_span(window, block_q, block_k, nq, nk)
        extra = dict(window=window, nq=nq)
        pairs = min(window, Tk) / Tk

    def q_block(j, i):
        if window is not None:
            i = _query_block(j, i, block_q, block_k)
        elif causal:
            i = jnp.maximum(i, (j * block_k) // block_q)
        return jnp.minimum(i, nq - 1)

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, q_block(j, i), 0))
    lane_spec = pl.BlockSpec(
        (1, 1, block_q, _LANES), lambda b, h, j, i: (b, h, q_block(j, i), 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h // g, j, 0))
    dkv_spec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0))
    f32 = jnp.float32
    # the steps that run: under the causal mask about half the grid's
    tiles = B * H * nk * steps * (0.5 if causal and window is None else 1.0)

    dk_h, dv_h, dq = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, **extra
        ),
        grid=(B, H, nk, steps),
        in_specs=[q_spec, kv_spec, kv_spec, lane_spec, lane_spec, q_spec],
        out_specs=[dkv_spec, dkv_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk, D), f32),
            jax.ShapeDtypeStruct((B, H, Tk, D), f32),
            jax.ShapeDtypeStruct((B, H, Tq, D), f32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), f32),
            pltpu.VMEM((block_k, D), f32),
            pltpu.VMEM((block_q, D), f32),
            pltpu.VMEM((block_q, D), f32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT,
        ),
        # five products and one exponent a tile; a tile fetches q and do,
        # lse and delta, reads and writes dq's block; a key block of a
        # query head fetches k and v and writes dk and dv
        cost_estimate=pl.CostEstimate(
            flops=int(10 * B * H * Tq * Tk * D * pairs),
            bytes_accessed=int(
                tiles * block_q * 2 * (D * (q.dtype.itemsize + 4) + _LANES * 4)
                + 2 * B * H * Tk * D * (k.dtype.itemsize + 4)),
            transcendentals=int(B * H * Tq * Tk * pairs),
        ),
        # the benchmark counts a backward pass by this name
        # (benchmark/trace_names.json `flash_bwd_count`)
        name="flash_bwd_dq" if window is None else "flash_bwd_window_dq",
        interpret=interpret_mode(),
    )(q, k, v, lse_rep, delta_rep, do)

    dk = dk_h.reshape(B, KVH, g, Tk, D).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, KVH, g, Tk, D).sum(axis=2).astype(v.dtype)
    return dq.astype(q.dtype), dk, dv


# ---------------------------------------------------------------------------
# Blockwise XLA fallback (forward + stats) and flash-2 backward
# ---------------------------------------------------------------------------


def _pad_kv(k, v, block_k):
    """Pad the KV sequence axis up to a block multiple. Returns
    (k, v, true_len); padded keys are masked out by callers via k_pos."""
    Tk = k.shape[2]
    pad = (-Tk) % block_k
    if pad:
        cfgpad = [(0, 0), (0, 0), (0, pad), (0, 0)]
        k = jnp.pad(k, cfgpad)
        v = jnp.pad(v, cfgpad)
    return k, v, Tk


def _fwd_xla_blockwise(q, k, v, *, causal, scale, block_k, window=None):
    """Scan over kv blocks, all q rows at once. [B,H,T,D] layout.

    Returns (o, lse) with lse [B,H,T] in f32. Handles any Tk (kv padded to
    a block multiple; padded keys masked).
    """
    B, H, Tq, D = q.shape
    KVH = k.shape[1]
    k, v, Tk = _pad_kv(k, v, block_k)
    g = H // KVH
    nk = k.shape[2] // block_k
    qf = q.astype(jnp.float32)
    kb = k.astype(jnp.float32).reshape(B, KVH, nk, block_k, D)
    vb = v.astype(jnp.float32).reshape(B, KVH, nk, block_k, D)
    kb = jnp.moveaxis(kb, 2, 0)  # [nk, B, KVH, bk, D]
    vb = jnp.moveaxis(vb, 2, 0)
    q_pos = jnp.arange(Tq)

    def body(carry, blk):
        acc, m_prev, l_prev = carry
        kj, vj, j = blk
        s = jnp.einsum(
            "bcgqd,bckd->bcgqk",
            qf.reshape(B, KVH, g, Tq, D),
            kj,
            preferred_element_type=jnp.float32,
        ).reshape(B, H, Tq, block_k)
        s = s * scale
        k_pos = j * block_k + jnp.arange(block_k)
        keep = k_pos[None, :] < Tk
        if causal:
            keep = keep & (q_pos[:, None] >= k_pos[None, :])
        if window is not None:
            keep = keep & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(keep, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[..., None])
        p = jnp.where(m_next[..., None] > _NEG_INF / 2, p, 0.0)
        l_next = alpha * l_prev + p.sum(axis=-1)
        pv = jnp.einsum(
            "bcgqk,bckd->bcgqd",
            p.reshape(B, KVH, g, Tq, block_k),
            vj,
            preferred_element_type=jnp.float32,
        ).reshape(B, H, Tq, D)
        acc = acc * alpha[..., None] + pv
        return (acc, m_next, l_next), None

    # init derived from qf so it inherits any device-varying mesh axes when
    # called under shard_map (scan carry in/out vma types must agree)
    init = (
        qf * 0.0,
        qf[..., 0] * 0.0 + _NEG_INF,
        qf[..., 0] * 0.0,
    )
    (acc, m, l), _ = jax.lax.scan(body, init, (kb, vb, jnp.arange(nk)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return o, lse


def _bwd_xla_blockwise(q, k, v, o, lse, do, *, causal, scale, block_k, dlse=None,
                       window=None):
    """Flash-2 backward as a scan over kv blocks. [B,H,T,D] layout.

    dlse: optional [B,H,Tq] cotangent for the lse output (ring attention
    merges blocks through lse); folds in as a delta shift since
    d lse_i / d s_ij = p_ij.
    """
    B, H, Tq, D = q.shape
    KVH, Tk_orig = k.shape[1], k.shape[2]
    k, v, Tk = _pad_kv(k, v, block_k)
    g = H // KVH
    nk = k.shape[2] // block_k
    qf = q.astype(jnp.float32).reshape(B, KVH, g, Tq, D)
    dof = do.astype(jnp.float32).reshape(B, KVH, g, Tq, D)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [B,H,Tq]
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = delta.reshape(B, KVH, g, Tq)
    lse_r = lse.reshape(B, KVH, g, Tq)
    kb = jnp.moveaxis(k.astype(jnp.float32).reshape(B, KVH, nk, block_k, D), 2, 0)
    vb = jnp.moveaxis(v.astype(jnp.float32).reshape(B, KVH, nk, block_k, D), 2, 0)
    q_pos = jnp.arange(Tq)

    def body(dq_acc, blk):
        kj, vj, j = blk
        s = jnp.einsum("bcgqd,bckd->bcgqk", qf, kj, preferred_element_type=jnp.float32)
        s = s * scale
        k_pos = j * block_k + jnp.arange(block_k)
        keep = k_pos[None, :] < Tk
        if causal:
            keep = keep & (q_pos[:, None] >= k_pos[None, :])
        if window is not None:
            keep = keep & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(keep, s, _NEG_INF)
        p = jnp.exp(s - lse_r[..., None])  # [B,KVH,g,Tq,bk]
        dv_j = jnp.einsum("bcgqk,bcgqd->bckd", p, dof, preferred_element_type=jnp.float32)
        dp = jnp.einsum("bcgqd,bckd->bcgqk", dof, vj, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum(
            "bcgqk,bckd->bcgqd", ds, kj, preferred_element_type=jnp.float32
        )
        dk_j = jnp.einsum("bcgqk,bcgqd->bckd", ds, qf, preferred_element_type=jnp.float32)
        return dq_acc, (dk_j, dv_j)

    dq0 = qf * 0.0  # derived from qf: inherits vma under shard_map
    dq, (dk, dv) = jax.lax.scan(body, dq0, (kb, vb, jnp.arange(nk)))
    dk = jnp.moveaxis(dk, 0, 2).reshape(B, KVH, -1, D)[:, :, :Tk_orig]
    dv = jnp.moveaxis(dv, 0, 2).reshape(B, KVH, -1, D)[:, :, :Tk_orig]
    return (
        dq.reshape(B, H, Tq, D).astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
    )


# ---------------------------------------------------------------------------
# Public op (custom VJP, BTHD layout)
# ---------------------------------------------------------------------------


def _pallas_ok(q_bhtd, k_bhtd, block_q, block_k) -> bool:
    B, H, Tq, D = q_bhtd.shape
    Tk = k_bhtd.shape[2]
    return (
        use_pallas()
        and D % _LANES == 0
        and Tq % block_q == 0
        and Tk % block_k == 0
        and H % k_bhtd.shape[1] == 0
    )



def _xla_bk(block_k: int, k) -> int:
    """Block size for the XLA fallback paths. Big tiles only help the Pallas
    kernels (amortizing Mosaic per-program overhead); the XLA scan's temps
    scale with block_k, so a 1024 auto-block would 8x its peak memory. Cap
    at the historical 128."""
    return min(block_k, 128, k.shape[2])

def _windowed(window):
    """The keyword a window adds to a kernel's or a fallback's call: none
    where there is no window, so that those calls are what they were."""
    return {} if window is None else {"window": window}


def _fwd_dispatch(q, k, v, causal, scale, block_q, block_k, window=None):
    """Pallas kernel when lowering for TPU and shapes tile; XLA otherwise."""
    w = _windowed(window)
    if not _pallas_ok(q, k, block_q, block_k):
        o, _ = _fwd_xla_blockwise(
            q, k, v, causal=causal, scale=scale, block_k=_xla_bk(block_k, k), **w
        )
        return o
    return platform_dispatch(
        lambda q, k, v: _flash_fwd_pallas(
            q, k, v, causal=causal, scale=scale, block_q=block_q, block_k=block_k, **w
        ),
        lambda q, k, v: _fwd_xla_blockwise(
            q, k, v, causal=causal, scale=scale, block_k=_xla_bk(block_k, k), **w
        )[0],
        q,
        k,
        v,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd(q, k, v, causal, scale, block_q, block_k, window=None):
    return _fwd_dispatch(q, k, v, causal, scale, block_q, block_k, window)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, window=None):
    # Both branches of the dispatch return (o, lse[B,H,Tq] f32); the lse
    # residual feeds the fused Pallas backward (no fwd recompute). Both are
    # named HERE, output and residual alike: a `jax.checkpoint` whose policy
    # saves these names then keeps what the backward reads and does not run
    # the kernel again (named outside the rule, `o` would be kept and the
    # kernel run again for `lse` alone). Outside a checkpoint a name is the
    # identity. `_flash_lse_fwd_rule` below names nothing: ring attention
    # calls it once a ring step, and every step's partials would be kept.
    o, lse = _fwd_lse_dispatch(q, k, v, causal, scale, block_q, block_k, window)
    o = checkpoint_name(o, FLASH_RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, FLASH_RESIDUAL_NAMES[1])
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, window, res, do):
    q, k, v, o, lse = res
    w = _windowed(window)
    if not _pallas_ok(q, k, block_q, block_k):
        bk = _xla_bk(block_k, k)
        return _bwd_xla_blockwise(
            q, k, v, o, lse, do, causal=causal, scale=scale, block_k=bk, **w
        )
    return platform_dispatch(
        lambda q, k, v, o, lse, do: _flash_bwd_pallas(
            q, k, v, o, lse, do, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, **w,
        ),
        lambda q, k, v, o, lse, do: _bwd_xla_blockwise(
            q, k, v, o, lse, do, causal=causal, scale=scale,
            block_k=_xla_bk(block_k, k), **w
        ),
        q, k, v, o, lse, do,
    )


_flash_bhtd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# Stats-returning variant: (o, lse) both differentiable. Ring attention
# merges per-block partials through lse, so its cotangent matters; it folds
# into the same kernels as a delta shift (see _flash_bwd_pallas).
# ---------------------------------------------------------------------------


def _fwd_lse_dispatch(q, k, v, causal, scale, block_q, block_k, window=None):
    w = _windowed(window)
    if not _pallas_ok(q, k, block_q, block_k):
        bk = _xla_bk(block_k, k)
        return _fwd_xla_blockwise(q, k, v, causal=causal, scale=scale, block_k=bk, **w)
    return platform_dispatch(
        lambda q, k, v: _flash_fwd_pallas(
            q, k, v, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, return_lse=True, **w,
        ),
        lambda q, k, v: _fwd_xla_blockwise(
            q, k, v, causal=causal, scale=scale, block_k=_xla_bk(block_k, k), **w
        ),
        q, k, v,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse_bhtd(q, k, v, causal, scale, block_q, block_k):
    return _fwd_lse_dispatch(q, k, v, causal, scale, block_q, block_k)


def _flash_lse_fwd_rule(q, k, v, causal, scale, block_q, block_k):
    o, lse = _fwd_lse_dispatch(q, k, v, causal, scale, block_q, block_k)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_bwd_rule(causal, scale, block_q, block_k, res, cts):
    q, k, v, o, lse = res
    do, dlse = cts
    if not _pallas_ok(q, k, block_q, block_k):
        bk = _xla_bk(block_k, k)
        return _bwd_xla_blockwise(
            q, k, v, o, lse, do, causal=causal, scale=scale, block_k=bk, dlse=dlse
        )
    return platform_dispatch(
        lambda q, k, v, o, lse, do, dlse: _flash_bwd_pallas(
            q, k, v, o, lse, do, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, dlse=dlse,
        ),
        lambda q, k, v, o, lse, do, dlse: _bwd_xla_blockwise(
            q, k, v, o, lse, do, causal=causal, scale=scale,
            block_k=_xla_bk(block_k, k), dlse=dlse,
        ),
        q, k, v, o, lse, do, dlse,
    )


_flash_lse_bhtd.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> "tuple[jax.Array, jax.Array]":
    """Flash attention returning (o, lse).

    Args as `flash_attention`; returns o [B, T, H, D] and the per-row
    logsumexp lse [B, H, T] (f32). Both outputs are differentiable — the
    building block for ring attention's block merges."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    block_q = block_q or _auto_block(q.shape[1])
    block_k = block_k or _auto_block(k.shape[1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o, lse = _flash_lse_bhtd(qt, kt, vt, causal, scale, block_q, block_k)
    return jnp.swapaxes(o, 1, 2), lse


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Multi-head / grouped-query flash attention.

    Args:
      q: [B, T, H, D]; k, v: [B, T, KVH, D] with H % KVH == 0 (GQA).
      causal: apply causal mask.
      window: a query at position i sees the keys i - window < j <= i
        (causal, self-attention: q and k of one length). Forward and
        backward visit the key blocks a query block's window reaches and
        no others; a window that cannot bind (>= T) is no window.
      scale: score scale, default 1/sqrt(D).
      block_q/block_k: kernel tile sizes; default picks the largest
        power-of-two <=1024 dividing each sequence length.
    Returns [B, T, H, D] in q's dtype.

    Heads narrower than a 128-lane tile (D = 64, 32, ..) reach the kernel
    as ops/paged_attention.py's do: 128 / D neighbouring kv heads side by
    side are one 128-wide head, and a query head is zero outside its own kv
    head's lanes; 128 / D times the products of a kernel that could tile D.
    A head wider than a tile that is not whole tiles, or values narrower
    than keys (v [B, T, KVH, Dv]), are padded with zeros to whole tiles of
    one width, and the output is Dv wide.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if window is not None:
        if not causal or q.shape[1] != k.shape[1] or window < 1:
            raise ValueError("a window is causal self-attention's: causal, "
                             "q and k of one length, window >= 1")
        if window >= k.shape[1]:
            window = None  # cannot bind: the causal kernels as they are
    KVH = k.shape[2]
    D, Dv = q.shape[3], v.shape[3]
    if Dv != D or (D > _LANES and D % _LANES):
        # keys and values of unlike widths, or a wide head that is not
        # whole tiles (latent attention's plain form: 192 and 128): zeros
        # up to whole tiles change no score, and a value's own lanes come
        # back as they were. What it costs a TRAINING row: every product of
        # the forward and the backward runs at 256 lanes where q k^T needs
        # 192 and p v 128, (2 x 256) / (192 + 128) = 1.6 x the products, and
        # q, k, v, o and the backward's float32 dq, dk, dv are 256 wide in
        # memory (dk and dv a HEAD here: 512 MB each at 2 x 8192 x 32); a
        # kernel that tiles 192 | 128 is the number a later change starts from
        wide = -(-max(D, Dv) // _LANES) * _LANES
        q, k, v = (jnp.pad(x, ((0, 0),) * 3 + ((0, wide - x.shape[3]),))
                   for x in (q, k, v))
        return flash_attention(q, k, v, causal, scale, block_q,
                               block_k, window)[..., :Dv]
    f = tile_factor(q.shape[2], KVH, q.shape[3])
    if f > 1:
        wide = (*k.shape[:2], KVH // f, f * k.shape[3])
        o = flash_attention(_tile_heads(q, KVH, f), k.reshape(wide),
                            v.reshape(wide), causal, scale, block_q, block_k,
                            window)
        return _untile_heads(o, KVH, f)
    block_q = block_q or _auto_block(q.shape[1])
    block_k = block_k or _auto_block(k.shape[1])
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,T,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = _flash_bhtd(qt, kt, vt, causal, scale, block_q, block_k,
                    *(() if window is None else (window,)))
    return jnp.swapaxes(o, 1, 2)
