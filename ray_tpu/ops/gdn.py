"""The gated delta rule (linear attention with a matrix of state per head)
and its one-token decode form.

    S'_t = alpha_t S_{t-1}                              S [dk, dv] per head
    S_t  = S'_t + beta_t k_t (v_t - S'_t^T k_t)^T       alpha_t = exp(g_t)
    o_t  = S_t^T q_t

State and arithmetic are float32 whatever the activations' type; the chunk
kernels' matrix products (`_dot`) are float32 operands at the DEFAULT matmul
precision, which on the TPU multiplies through bfloat16 passes: against the
scan at `highest` a chunk's outputs differ by 8e-4 of a scale of 0.25 and
the state by 7e-3 in both forms, where Precision.HIGHEST reads 5e-7 and 5e-6
for a sixth more time (the channel form alone on a v5e, PR 52; not taken,
so that the scalar form stays the program it was). `gdn_step` has no matrix
product and is exact to float32 rounding. The state
of all H heads is ONE array [dk, H * dv]: key rows on sublanes, head h's
value lanes at h*dv .. (h+1)*dv, so a slot's state is one contiguous block
with no lane padding where H * dv is a multiple of 128 (a [dk, dv] matrix
per head with dv = 192 would be stored 256 lanes wide). The layout is this
module's own business: whoever allocates state asks `state_shape`.

`gdn_chunk` runs a sequence (the prefill programs) from a carried state: a
Pallas kernel over blocks of 64 positions with the state resident in VMEM,
the WY form within a block. With G_t the running sum of g inside the block,
A[t,s] = exp(G_t - G_s) beta_s k_t.k_s (s < t) and D the rows
d_t = v_t - S'_t^T k_t:

    D   = (I + A)^-1 (V - diag(exp G) K S_0)
    O   = diag(exp G) Q S_0 + (tril(Q K^T) * exp(G_t - G_s) beta_s) D
    S_C = exp(G_C) S_0 + (K^T diag(beta_s exp(G_C - G_s))) D

(I + A)^-1 is built row by row (forward substitution, exact in float32; a
Neumann product would cancel badly where neighbouring keys are alike and
beta nears 2). Every decay is exp of a difference that is <= 0. A position
with g = 0 and beta = 0 leaves the state as it was, which is how padding is
passed over. An XLA `lax.scan` of the recurrence elsewhere.

The decay may be a VECTOR over the key channels (Kimi Delta Attention,
arXiv:2510.26692): g_t in R^dk a head, S'_t = Diag(exp g_t) S_{t-1}, the
rest as above. Both ops take g [.., H] (the scalar form, the programs it
always had) or [.., H, dk] (the channel form); which is a static property of
the call. With G_t the running sum (now a row of dk) the WY form keeps its
shape and the decays move inside the key products:

    A[t,s] = beta_s sum_i k_t,i k_s,i exp(G_t,i - G_s,i)        (s < t)
    P[t,s] = beta_s sum_i q_t,i k_s,i exp(G_t,i - G_s,i)        (s <= t)
    D   = (I + A)^-1 (V - (K * exp G) S_0)
    O   = (Q * exp G) S_0 + P D
    S_C = Diag(exp G_C) S_0 + (K * exp(G_C - G) * beta)^T D

A and P no longer factor into a matrix product times exp(G_t - G_s), and the
factoring (k_t * exp G_t) . (k_s * exp -G_s) overflows float32 inside a
block (a channel may decay by e^-20 a position). The exponent rule is kept
by sub-blocks of 16 positions: for t in sub-block b and s in an EARLIER one
the reference R_b = G at b's first position lies between them, so
exp(G_t - R_b) and exp(R_b - G_s) are both of exponents <= 0 and the
off-diagonal sub-blocks stay matrix products (one a sub-block); the 16 x 16
diagonal sub-blocks are computed pair by pair over the channels on the
vector unit, a column of all four a pass, exp(min(G_t - G_s, 0)) masked to
the pairs it is meant for.

`gdn_step` is decode: one token for every slot, updating ONE layer of the
engine's whole state array [layers, B, dk, H*dv] in place (the layer rides
as a scalar prefetch, as in ops/ssm.py). One grid program a slot; a slot
that is not `live` moves nothing: its program's blocks are those of the
nearest live slot (prefetched scalars), so the pipeline neither fetches nor
writes them again, and its state stays bit for bit.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import (
    interpret_mode,
    platform_dispatch,
    slot_order,
    use_pallas,
)

_LANES = 128
_ROWS = 8
_BLOCK = 64  # positions a program of the chunk kernel takes
_SUB = 16    # ... and a sub-block of its channel form (module docstring)
_F32 = jnp.float32


def state_shape(layers: int, slots: int, heads: int, dk: int,
                dv: int) -> Tuple[int, ...]:
    """The shape of a delta-rule state array: whoever allocates one asks
    here."""
    return (layers, slots, dk, heads * dv)


def _one_step(S, q, k, v, alpha, beta):
    """S [B,dk,H,dv]; q, k [B,H,dk]; v [B,H,dv]; beta [B,H]; alpha [B,H],
    or [B,H,dk]: a decay a key channel."""
    if alpha.ndim == 3:
        S = S * jnp.swapaxes(alpha, 1, 2)[..., None]
    else:
        S = S * alpha[:, None, :, None]
    d = beta[..., None] * (v - jnp.einsum("bihj,bhi->bhj", S, k))
    S = S + jnp.einsum("bhi,bhj->bihj", k, d)
    return S, jnp.einsum("bihj,bhi->bhj", S, q)


# ---------------------------------------------------------------------------
# a sequence
# ---------------------------------------------------------------------------


def gdn_chunk_reference(q, k, v, g, beta, s0):
    """q, k [B,T,H,dk]; v [B,T,H,dv]; g (log decay, <= 0) [B,T,H] or
    [B,T,H,dk]; beta [B,T,H]; s0 [B,dk,H*dv] f32 -> (o [B,T,H,dv] f32,
    s1 [B,dk,H*dv] f32)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        return _one_step(S, q_t, k_t, v_t, jnp.exp(g_t), b_t)

    xs = tuple(jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        s1, o = jax.lax.scan(step, s0.astype(_F32).reshape(B, dk, H, dv), xs)
    return jnp.moveaxis(o, 0, 1), s1.reshape(B, dk, H * dv)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, cols_ref, rows_ref, s0_ref,
                  whole_ref, o_ref, s1_ref, s_scr, *, block, n_blocks):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    C = block
    q, k, kt, v = q_ref[0, 0], k_ref[0, 0], kt_ref[0, 0, 0], v_ref[0, 0]
    cols, rows = cols_ref[0, 0], rows_ref[0, 0, 0]
    Gc, bc = cols[:, 0:1], cols[:, 1:2]      # [C,1]: a position a sublane
    Gr, br = rows[0:1, :], rows[1:2, :]      # [1,C]: a position a lane
    gC = Gc[C - 1:C, :]                      # the block's whole decay
    S = s_scr[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # A transposed: AT[s,t] = beta_s k_s.k_t exp(G_t - G_s), s < t
    upper = row < col
    AT = jnp.where(
        upper, _dot(k * bc, kt) * jnp.exp(jnp.where(upper, Gr - Gc, 0.0)), 0.0)
    # (I + A)^-1, a row at a time: row i is e_i - sum_s A[i,s] row s, and
    # until its turn a row holds e_i
    Tm = (row == col).astype(_F32)
    for i in range(1, C):
        Tm = jnp.where(
            row == i,
            Tm - jnp.sum(AT[:, i:i + 1] * Tm, axis=0, keepdims=True), Tm)
    eg = jnp.exp(Gc)
    D = _dot(Tm, v - _dot(k * eg, S))
    lower = row >= col
    P = jnp.where(
        lower,
        _dot(q, kt) * br * jnp.exp(jnp.where(lower, Gc - Gr, 0.0)), 0.0)
    o_ref[0, 0] = _dot(q * eg, S) + _dot(P, D)
    # exp(G_C) as a scalar (a [1,1] vector does not go over a whole tile)
    S = (whole_ref[pl.program_id(0), pl.program_id(1), t] * S
         + _dot(kt * (br * jnp.exp(gC - Gr)), D))
    s_scr[...] = S

    @pl.when(t == n_blocks - 1)
    def _finish():
        s1_ref[0, 0] = S


def _chunk_pallas(q, k, v, g, beta, s0):
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = _BLOCK
    n = T // C

    def heads_first(a):
        return jnp.moveaxis(a.astype(_F32), 2, 1)  # [B,H,T,..]

    qh, kh, vh = heads_first(q), heads_first(k), heads_first(v)
    kt = jnp.swapaxes(kh.reshape(B, H, n, C, dk), -1, -2)
    G = jnp.cumsum(heads_first(g).reshape(B, H, n, C), axis=-1)
    bh = heads_first(beta).reshape(B, H, n, C)
    cols = jnp.stack([G, bh], axis=-1).reshape(B, H, T, 2)
    rows = jnp.stack([G, bh], axis=-2)             # [B,H,n,2,C]
    s0h = jnp.moveaxis(s0.astype(_F32).reshape(B, dk, H, dv), 2, 1)

    def seq(width):
        return pl.BlockSpec((1, 1, C, width), lambda b, h, t: (b, h, t, 0))

    state = pl.BlockSpec((1, 1, dk, dv), lambda b, h, t: (b, h, 0, 0))
    o, s1 = pl.pallas_call(
        functools.partial(_chunk_kernel, block=C, n_blocks=n),
        grid=(B, H, n),
        in_specs=[seq(dk), seq(dk),
                  pl.BlockSpec((1, 1, 1, dk, C),
                               lambda b, h, t: (b, h, t, 0, 0)),
                  seq(dv), seq(2),
                  pl.BlockSpec((1, 1, 1, 2, C),
                               lambda b, h, t: (b, h, t, 0, 0)),
                  state,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[seq(dv), state],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gdn_chunk",
        interpret=interpret_mode(),
    )(qh, kh, kt, vh, cols, rows, s0h, jnp.exp(G[..., -1]))
    return (jnp.moveaxis(o, 1, 2),
            jnp.moveaxis(s1, 1, 2).reshape(B, dk, H * dv))


def _channel_chunk_kernel(q_ref, k_ref, kt_ref, v_ref, g_ref, gt_ref,
                          bcol_ref, brow_ref, s0_ref, o_ref, s1_ref, s_scr,
                          *, block, sub, n_blocks):
    """`_chunk_kernel` for a decay a key channel (module docstring): G
    [C,dk] a position a sublane, Gt [dk,C] a position a lane."""
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    C, c = block, sub
    q, k, kt, v = q_ref[0, 0], k_ref[0, 0], kt_ref[0, 0, 0], v_ref[0, 0]
    G, Gt = g_ref[0, 0], gt_ref[0, 0, 0]
    bc, br = bcol_ref[0, 0], brow_ref[0, 0, 0]   # [C,1], [1,C]
    S = s_scr[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    at = jax.lax.broadcasted_iota(jnp.int32, k.shape, 0)    # a position a row
    att = jax.lax.broadcasted_iota(jnp.int32, kt.shape, 1)  # ... a lane

    def decayed(x, e, first, end, along):
        """x * exp(e) at positions first .. end - 1 (`along`: the iota of
        x's position axis), where e <= 0; 0 elsewhere."""
        keep = (along >= first) & (along < end)
        return jnp.where(keep, x * jnp.exp(jnp.minimum(e, 0.0)), 0.0)

    def row_of_each(x, j):
        """Row j of every sub-block of x [C, w], over that sub-block's rows."""
        return jnp.concatenate(
            [jnp.broadcast_to(x[b * c + j:b * c + j + 1, :], (c, x.shape[1]))
             for b in range(C // c)], axis=0)

    # AT[s,t] = beta_s A[t,s] (s < t) and P[t,s] (s <= t), both before
    # beta. Off the diagonal sub-blocks, from sub-block b's reference R_b
    AT = jnp.zeros((C, C), _F32)
    P = jnp.zeros((C, C), _F32)
    for b in range(1, C // c):
        lo, hi = b * c, (b + 1) * c
        R, Rt = G[lo:lo + 1, :], Gt[:, lo:lo + 1]
        AT = AT + _dot(decayed(k, R - G, 0, lo, at),
                       decayed(kt, Gt - Rt, lo, hi, att))
        P = P + _dot(decayed(q, G - R, lo, hi, at),
                     decayed(kt, Rt - Gt, 0, lo, att))
    # the diagonal sub-blocks, column j of all of them at a time
    for j in range(c):
        Gj, kj = row_of_each(G, j), row_of_each(k, j)
        colP = jnp.sum(q * kj * jnp.exp(jnp.minimum(G - Gj, 0.0)),
                       axis=1, keepdims=True)
        colA = jnp.sum(k * kj * jnp.exp(jnp.minimum(Gj - G, 0.0)),
                       axis=1, keepdims=True)
        mine = col == row - (row & (c - 1)) + j
        P = jnp.where(mine & (row >= col), colP, P)
        AT = jnp.where(mine & (row < col), colA, AT)
    AT, P = AT * bc, P * br
    Tm = (row == col).astype(_F32)
    for i in range(1, C):
        Tm = jnp.where(
            row == i,
            Tm - jnp.sum(AT[:, i:i + 1] * Tm, axis=0, keepdims=True), Tm)
    eg = jnp.exp(G)
    D = _dot(Tm, v - _dot(k * eg, S))
    o_ref[0, 0] = _dot(q * eg, S) + _dot(P, D)
    gC = Gt[:, C - 1:C]                      # [dk,1]: the block's whole decay
    S = jnp.exp(gC) * S + _dot(kt * jnp.exp(gC - Gt) * br, D)
    s_scr[...] = S

    @pl.when(t == n_blocks - 1)
    def _finish():
        s1_ref[0, 0] = S


def _channel_chunk_pallas(q, k, v, g, beta, s0):
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = _BLOCK
    n = T // C

    def heads_first(a):
        return jnp.moveaxis(a.astype(_F32), 2, 1)  # [B,H,T,..]

    def lanes(a):  # [B,H,T,w] -> [B,H,n,w,C]: a position a lane
        return jnp.swapaxes(a.reshape(B, H, n, C, a.shape[-1]), -1, -2)

    qh, kh, vh = heads_first(q), heads_first(k), heads_first(v)
    G = jnp.cumsum(heads_first(g).reshape(B, H, n, C, dk),
                   axis=3).reshape(B, H, T, dk)
    bh = heads_first(beta)[..., None]              # [B,H,T,1]
    s0h = jnp.moveaxis(s0.astype(_F32).reshape(B, dk, H, dv), 2, 1)

    def seq(width):
        return pl.BlockSpec((1, 1, C, width), lambda b, h, t: (b, h, t, 0))

    def across(width):
        return pl.BlockSpec((1, 1, 1, width, C),
                            lambda b, h, t: (b, h, t, 0, 0))

    state = pl.BlockSpec((1, 1, dk, dv), lambda b, h, t: (b, h, 0, 0))
    o, s1 = pl.pallas_call(
        functools.partial(_channel_chunk_kernel, block=C, sub=_SUB,
                          n_blocks=n),
        grid=(B, H, n),
        in_specs=[seq(dk), seq(dk), across(dk), seq(dv), seq(dk), across(dk),
                  seq(1), across(1), state],
        out_specs=[seq(dv), state],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gdn_chunk",
        interpret=interpret_mode(),
    )(qh, kh, lanes(kh), vh, G, lanes(G), bh, lanes(bh), s0h)
    return (jnp.moveaxis(o, 1, 2),
            jnp.moveaxis(s1, 1, 2).reshape(B, dk, H * dv))


def gdn_chunk(q, k, v, g, beta, s0, force_xla: bool = False):
    """The recurrence of one layer over a sequence, from state s0.

    q, k [B,T,H,dk] (normalised and scaled by the caller), v [B,T,H,dv],
    g (log decay, <= 0) [B,T,H] or, a decay a key channel, [B,T,H,dk], and
    beta [B,T,H] (both 0 at a position leave the state as it was: padding),
    s0 [B,dk,H*dv].
    -> (o [B,T,H,dv] float32, final state [B,dk,H*dv] float32)."""
    T, dk, dv = q.shape[1], q.shape[-1], v.shape[-1]
    ok = (use_pallas() and T % _BLOCK == 0 and dk % _ROWS == 0
          and dv % _ROWS == 0)
    if force_xla or not ok:
        return gdn_chunk_reference(q, k, v, g, beta, s0)
    kernel = _channel_chunk_pallas if g.ndim == q.ndim else _chunk_pallas
    return platform_dispatch(kernel, gdn_chunk_reference,
                             q, k, v, g, beta, s0)


# ---------------------------------------------------------------------------
# one token for every slot
# ---------------------------------------------------------------------------


def gdn_step_reference(state, layer, q, k, v, g, beta, live):
    """state [L,B,dk,H*dv] f32; q, k [B,H,dk]; v [B,H,dv]; beta [B,H];
    g [B,H] or [B,H,dk]; live [B] bool -> (o [B,H,dv] f32, state with the
    live slots of layer `layer` advanced and every other slot untouched)."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    f = lambda a: a.astype(_F32)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        new, o = _one_step(f(old).reshape(B, dk, H, dv), f(q), f(k), f(v),
                           jnp.exp(f(g)), f(beta))
    new = jnp.where(live[:, None, None], new.reshape(old.shape), f(old))
    return o, jax.lax.dynamic_update_index_in_dim(
        state, new.astype(state.dtype), layer, 0)


def _unit(dv: int) -> int:
    """Lanes the step kernel takes at a time: whole heads AND whole
    128-lane tiles (dv = 192: two heads, 384 lanes)."""
    return dv * _LANES // math.gcd(dv, _LANES)


def _step_kernel(src_ref, live_ref, layer_ref, kq_ref, rows_ref, s_ref,
                 o_ref, so_ref, *, heads, dv, unit, channel=False):
    """`channel`: the decay is a key channel's, exp(g) a third [dk, H]
    operand beside k and q, and `rows_ref` holds beta and v alone."""
    del layer_ref  # the block specs read it
    b = pl.program_id(0)
    per = unit // dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, unit), 1)

    def wide(cols, first):
        """cols [dk, H] -> [dk, unit]: head first + j's column over the
        lanes of its values."""
        out = cols[:, first:first + 1]
        for j in range(1, per):
            out = jnp.where(lane >= j * dv, cols[:, first + j:first + j + 1],
                            out)
        return out

    @pl.when(live_ref[b] > 0)
    def _advance():
        kt, qt = kq_ref[0, 0], kq_ref[0, 1]           # [dk, H]
        at_ = kq_ref[0, 2] if channel else None       # exp(g), likewise
        for u in range(heads * dv // unit):
            at = slice(u * unit, (u + 1) * unit)
            if channel:
                beta, v = (rows_ref[0, i:i + 1, at] for i in range(2))
                alpha = wide(at_, u * per)
            else:
                alpha, beta, v = (rows_ref[0, i:i + 1, at] for i in range(3))
            kx, qx = wide(kt, u * per), wide(qt, u * per)
            S = s_ref[0, 0, :, at] * alpha
            d = beta * (v - jnp.sum(S * kx, axis=0, keepdims=True))
            S = S + kx * d
            so_ref[0, 0, :, at] = S
            o_ref[0, :, at] = jnp.sum(S * qx, axis=0, keepdims=True)

    @pl.when(live_ref[b] == 0)
    def _pass():
        o_ref[...] = jnp.zeros_like(o_ref)

    # no slot is live at all: the one block the pipeline holds goes back
    # as it came
    @pl.when((b == 0) & (live_ref[src_ref[0]] == 0))
    def _keep():
        so_ref[...] = s_ref[...]


def _step_pallas(state, layer, q, k, v, g, beta, live):
    _, B, dk, lanes = state.shape
    H, dv = q.shape[1], v.shape[-1]
    live = live.astype(jnp.int32)
    src = slot_order(live > 0)

    def row(a):  # [B,H] -> [B,H*dv]: a head's scalar over its value lanes
        return jnp.repeat(a.astype(_F32), dv, axis=-1)

    channel = g.ndim == q.ndim
    alpha = jnp.exp(g.astype(_F32))
    rows = jnp.stack(([] if channel else [row(alpha)]) + [
        row(beta), v.astype(_F32).reshape(B, H * dv)], axis=1)
    kq = jnp.swapaxes(jnp.stack(
        [k, q] + ([alpha] if channel else []), axis=1).astype(_F32), -1, -2)
    slab = pl.BlockSpec((1, 1, dk, lanes),
                        lambda b, src, live, l: (l[0], src[b], 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=H, dv=dv, unit=_unit(dv),
                          channel=channel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, *kq.shape[1:]), lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec((1, *rows.shape[1:]), lambda b, *_: (b, 0, 0)),
                slab],
            out_specs=[pl.BlockSpec((1, 1, lanes), lambda b, *_: (b, 0, 0)),
                       slab],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, lanes), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        # operand 5 (the scalar prefetches count) is the state: in place
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            # a dead program leans on its neighbour's blocks: in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        name="gdn_step",
        interpret=interpret_mode(),
    )(src, live, jnp.asarray(layer, jnp.int32).reshape(1), kq, rows, state)
    return o.reshape(B, H, dv), state


def gdn_step(state, layer, q, k, v, g, beta, live, force_xla: bool = False):
    """Decode: advance layer `layer` of the whole state [L,B,dk,H*dv]
    (float32) by one token for every slot that is `live` [B], in place;
    the others' state is untouched and their output zero. q, k [B,H,dk];
    v [B,H,dv]; beta [B,H]; g [B,H] or, a decay a key channel, [B,H,dk].
    -> (o [B,H,dv] float32, state)."""
    dk, lanes = state.shape[2:]
    dv = v.shape[-1]
    ok = (use_pallas() and state.dtype == _F32 and dk % _ROWS == 0
          and lanes % _unit(dv) == 0)
    if force_xla or not ok:
        return gdn_step_reference(state, layer, q, k, v, g, beta, live)
    return platform_dispatch(_step_pallas, gdn_step_reference,
                             state, layer, q, k, v, g, beta, live)
