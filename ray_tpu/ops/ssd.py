"""The scalar-decay state-space recurrence (Mamba-2, "SSD") and its
one-token decode form.

    S_t = a_t S_{t-1} + B_t (dt_t x_t)^T       S [N, P] per head
    y_t = S_t^T C_t                            a_t = exp(dt_t A), a scalar

H heads of P lanes; ONE decay a head and token (Mamba-1, ops/ssm.py, has
one per channel and state row); B_t and C_t [N] are shared by the heads of
a group (G groups). State and arithmetic are float32 whatever the
activations' type. The state of all H heads is ONE array [N, H * P]: state
rows on sublanes, head h's lanes at h*P .. (h+1)*P, so a slot's state is
one contiguous block of whole 128-lane tiles. The layout is this module's
own business: whoever allocates state asks `state_shape`. The skip term
D x_t is the caller's (an elementwise product XLA fuses).

`ssd_chunk` runs a sequence (the prefill programs) from a carried state in
the chunked dual form: inside a block of L positions, with G_t the running
sum of dt A,

    Y   = diag(exp G) C S_0 + (tril(C B^T) * exp(G_t - G_s)) (dt x)
    S_L = exp(G_L) S_0 + (B * exp(G_L - G_s))^T (dt x)

so the work is products on the MXU and the state is handed over once a
block. Every decay is exp of a difference that is <= 0 (the factored form
exp(G_t) exp(-G_s) overflows where dt is large: nothing clamps it). A
position with dt = 0 leaves the state as it was, which is how padding is
passed over. The Pallas kernel takes one head a program with the state
resident in VMEM over the blocks (256 positions where they divide the
sequence: the engine's chunk is one block); C B^T is one product for all
the heads of a group, made by XLA before it. Elsewhere the same blocks as
`jnp` products under a `lax.scan`.

`ssd_step` is decode: one token for every slot, updating ONE layer of the
engine's whole state array [layers, B, N, H*P] in place (the layer rides
as a scalar prefetch). One grid program a slot; a slot that is not `live`
moves nothing: its program's blocks are those of the nearest live slot
(`dispatch.slot_order`), so the pipeline neither fetches nor writes them
again, and its state stays bit for bit. A step's time is the live slots'
state, read and written once.
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
    slot_order,
    use_pallas,
)

_LANES = 128
_ROWS = 8
_BLOCKS = (256, 128, 64)  # positions a program of the chunk kernel takes
_XLA_BLOCK = 64
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def state_shape(layers: int, slots: int, heads: int, head_dim: int,
                d_state: int) -> Tuple[int, ...]:
    """The shape of a state array: whoever allocates one asks here."""
    return (layers, slots, d_state, heads * head_dim)


# ---------------------------------------------------------------------------
# a sequence
# ---------------------------------------------------------------------------


def ssd_chunk_reference(x, dt, A, Bm, Cm, s0):
    """x [B,T,H,P]; dt [B,T,H] (>= 0); A [H] (negative); Bm, Cm [B,T,G,N];
    s0 [B,N,H*P] f32 -> (y [B,T,H,P] f32, s1 [B,N,H*P] f32): the dual form
    over blocks of 64 positions, the rest of the last block padding."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    L = min(_XLA_BLOCK, T)
    n = -(-T // L)

    def blocks(a, *tail):  # [B,T,..] -> [n,B,L,*tail], zeros past T
        a = jnp.pad(a.astype(_F32), ((0, 0), (0, n * L - T))
                    + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(B, n, L, *tail), 1, 0)

    lower = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None, None]
    Ag = A.astype(_F32).reshape(G, R)

    def block(S, xs):  # S [B,N,G,R,P]
        x_b, dt_b, B_b, C_b = xs
        Gc = jnp.cumsum(dt_b * Ag, axis=1)                   # [B,L,G,R]
        decay = jnp.where(lower, jnp.exp(jnp.where(
            lower, Gc[:, :, None] - Gc[:, None], 0.0)), 0.0)  # [B,t,s,G,R]
        cb = jnp.einsum("btgn,bsgn->btsg", C_b, B_b)
        dtx = dt_b[..., None] * x_b
        y = (jnp.einsum("btsgr,bsgrp->btgrp", cb[..., None] * decay, dtx)
             + jnp.exp(Gc)[..., None]
             * jnp.einsum("btgn,bngrp->btgrp", C_b, S))
        w = jnp.exp(Gc[:, -1:] - Gc)[..., None] * dtx
        S = (jnp.exp(Gc[:, -1])[:, None, :, :, None] * S
             + jnp.einsum("bsgn,bsgrp->bngrp", B_b, w))
        return S, y

    with jax.default_matmul_precision("highest"):
        s1, y = jax.lax.scan(
            block, s0.astype(_F32).reshape(B, N, G, R, P),
            (blocks(x, G, R, P), blocks(dt, G, R), blocks(Bm, G, N),
             blocks(Cm, G, N)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, n * L, H, P)[:, :T]
    return y, s1.reshape(B, N, H * P)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32, precision=_HIGHEST)


def _chunk_kernel(x_ref, c_ref, bt_ref, cb_ref, col_ref, row_ref, s0_ref,
                  whole_ref, y_ref, s1_ref, s_scr, *, block, n_blocks):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0]

    L = block
    dtx, Cm = x_ref[0, 0], c_ref[0, 0]           # [L,P], [L,N]
    Bt, cb = bt_ref[0, 0, 0], cb_ref[0, 0, 0]    # [N,L], [L,L]
    Gc, Gr = col_ref[0, 0], row_ref[0, 0, 0]     # [L,1], [1,L]
    S = s_scr[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    lower = row >= col
    M = jnp.where(lower, cb * jnp.exp(jnp.where(lower, Gc - Gr, 0.0)), 0.0)
    y_ref[0, 0] = jnp.exp(Gc) * _dot(Cm, S) + _dot(M, dtx)
    # exp(G_L) as a scalar (a [1,1] vector does not go over a whole tile)
    S = (whole_ref[pl.program_id(0), pl.program_id(1), t] * S
         + _dot(Bt * jnp.exp(Gc[L - 1:L, :] - Gr), dtx))
    s_scr[...] = S

    @pl.when(t == n_blocks - 1)
    def _finish():
        s1_ref[0, 0] = S


def _chunk_pallas(x, dt, A, Bm, Cm, s0):
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    R = H // G
    L = next(c for c in _BLOCKS if T % c == 0)
    n = T // L
    dt = dt.astype(_F32)

    def first(a, axis):  # [B,T,heads or groups,..] -> [B,.,T,..]
        return jnp.moveaxis(a.astype(_F32), axis, 1)

    dtx = first(dt[..., None] * x.astype(_F32), 2)            # [B,H,T,P]
    Gc = jnp.cumsum(first(dt * A.astype(_F32), 2).reshape(B, H, n, L), -1)
    Cg, Bg = first(Cm, 2), first(Bm, 2)                       # [B,G,T,N]
    Bb = Bg.reshape(B, G, n, L, N)
    cb = jnp.einsum("bgmtn,bgmsn->bgmts", Cg.reshape(B, G, n, L, N), Bb,
                    precision=_HIGHEST)
    s0h = jnp.moveaxis(s0.astype(_F32).reshape(B, N, H, P), 2, 1)

    def seq(width, per):
        return pl.BlockSpec((1, 1, L, width),
                            lambda b, h, t: (b, h // per, t, 0))

    def square(rows, cols, per):
        return pl.BlockSpec((1, 1, 1, rows, cols),
                            lambda b, h, t: (b, h // per, t, 0, 0))

    state = pl.BlockSpec((1, 1, N, P), lambda b, h, t: (b, h, 0, 0))
    y, s1 = pl.pallas_call(
        functools.partial(_chunk_kernel, block=L, n_blocks=n),
        grid=(B, H, n),
        in_specs=[seq(P, 1), seq(N, R), square(N, L, R), square(L, L, R),
                  seq(1, 1), square(1, L, 1), state,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[seq(P, 1), state],
        out_shape=[jax.ShapeDtypeStruct((B, H, T, P), _F32),
                   jax.ShapeDtypeStruct((B, H, N, P), _F32)],
        scratch_shapes=[pltpu.VMEM((N, P), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssd_chunk",
        interpret=interpret_mode(),
    )(dtx, Cg, jnp.swapaxes(Bb, -1, -2), cb, Gc.reshape(B, H, T, 1),
      Gc[:, :, :, None, :], s0h, jnp.exp(Gc[..., -1]))
    return (jnp.moveaxis(y, 1, 2),
            jnp.moveaxis(s1, 1, 2).reshape(B, N, H * P))


def ssd_chunk(x, dt, A, Bm, Cm, s0, force_xla: bool = False):
    """The recurrence of one layer over a sequence, from state s0.

    x [B,T,H,P] (after the conv and its silu), dt [B,T,H] (after softplus;
    0 at a position leaves the state as it was: padding), A [H] (negative),
    Bm / Cm [B,T,G,N] (G divides H), s0 [B,N,H*P].
    -> (y [B,T,H,P] float32 without the skip term, final state [B,N,H*P]
    float32)."""
    T, P, N = x.shape[1], x.shape[-1], Bm.shape[-1]
    ok = (use_pallas() and T % _BLOCKS[-1] == 0 and P % _ROWS == 0
          and N % _ROWS == 0)
    if force_xla or not ok:
        return ssd_chunk_reference(x, dt, A, Bm, Cm, s0)
    return platform_dispatch(_chunk_pallas, ssd_chunk_reference,
                             x, dt, A, Bm, Cm, s0)


# ---------------------------------------------------------------------------
# one token for every slot
# ---------------------------------------------------------------------------


def ssd_step_reference(state, layer, x, dt, A, Bm, Cm, live):
    """state [L,B,N,H*P] f32; x [B,H,P]; dt [B,H]; A [H]; Bm, Cm [B,G,N];
    live [B] bool -> (y [B,H,P] f32, state with the live slots of layer
    `layer` advanced and every other slot untouched)."""
    B, H, P = x.shape
    G, N = Bm.shape[1:]
    R = H // G
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    f = lambda a: a.astype(_F32)  # noqa: E731
    dt = f(dt)
    a = jnp.exp(dt * f(A)).reshape(B, 1, G, R, 1)
    dtx = (dt[..., None] * f(x)).reshape(B, 1, G, R, P)
    S = (a * f(old).reshape(B, N, G, R, P)
         + jnp.swapaxes(f(Bm), 1, 2)[..., None, None] * dtx)
    y = jnp.sum(S * jnp.swapaxes(f(Cm), 1, 2)[..., None, None], axis=1)
    new = jnp.where(live[:, None, None], S.reshape(old.shape), f(old))
    return y.reshape(B, H, P), jax.lax.dynamic_update_index_in_dim(
        state, new.astype(state.dtype), layer, 0)


def _unit(lanes: int) -> int:
    """Lanes the step kernel takes at a time."""
    return 512 if lanes % 512 == 0 else _LANES


def _step_kernel(src_ref, live_ref, layer_ref, bc_ref, rows_ref, s_ref,
                 o_ref, so_ref, *, groups, unit):
    del layer_ref  # the block specs read it
    b = pl.program_id(0)
    N, lanes = s_ref.shape[2:]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

    def column(i):
        """Row i of the slot's B and C [2G, N] as a column [N, 1]: a
        state row a sublane."""
        wide = jnp.broadcast_to(bc_ref[0, i:i + 1, :], (N, N))
        return jnp.sum(jnp.where(eye, wide, 0.0), axis=1, keepdims=True)

    @pl.when(live_ref[b] > 0)
    def _advance():
        per = lanes // groups
        for g in range(groups):
            Bc, Cc = column(g), column(groups + g)
            for u in range(per // unit):
                at = slice(g * per + u * unit, g * per + (u + 1) * unit)
                S = (s_ref[0, 0, :, at] * rows_ref[0, 0:1, at]
                     + Bc * rows_ref[0, 1:2, at])
                so_ref[0, 0, :, at] = S
                o_ref[0, :, at] = jnp.sum(S * Cc, axis=0, keepdims=True)

    @pl.when(live_ref[b] == 0)
    def _pass():
        o_ref[...] = jnp.zeros_like(o_ref)

    # no slot is live at all: the one block the pipeline holds goes back
    # as it came
    @pl.when((b == 0) & (live_ref[src_ref[0]] == 0))
    def _keep():
        so_ref[...] = s_ref[...]


def _step_pallas(state, layer, x, dt, A, Bm, Cm, live):
    _, B, N, lanes = state.shape
    H, P = x.shape[1:]
    G = Bm.shape[1]
    live = live.astype(jnp.int32)
    src = slot_order(live > 0)
    dt = dt.astype(_F32)
    # a head's decay over its lanes, and dt x
    rows = jnp.stack([
        jnp.repeat(jnp.exp(dt * A.astype(_F32)), P, axis=-1),
        (dt[..., None] * x.astype(_F32)).reshape(B, lanes)], axis=1)
    bc = jnp.concatenate([Bm, Cm], axis=1).astype(_F32)        # [B,2G,N]
    slab = pl.BlockSpec((1, 1, N, lanes),
                        lambda b, src, live, l: (l[0], src[b], 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, groups=G, unit=_unit(lanes // G)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, 2 * G, N), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((1, 2, lanes), lambda b, *_: (b, 0, 0)),
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
        name="ssd_step",
        interpret=interpret_mode(),
    )(src, live, jnp.asarray(layer, jnp.int32).reshape(1), bc, rows, state)
    return o.reshape(B, H, P), state


def ssd_step(state, layer, x, dt, A, Bm, Cm, live, force_xla: bool = False):
    """Decode: advance layer `layer` of the whole state [L,B,N,H*P]
    (float32) by one token for every slot that is `live` [B], in place; the
    others' state is untouched and their output zero. x [B,H,P]; dt [B,H];
    A [H]; Bm, Cm [B,G,N]. -> (y [B,H,P] float32 without the skip term,
    state)."""
    N, lanes = state.shape[2:]
    G = Bm.shape[1]
    ok = (use_pallas() and state.dtype == _F32 and N % _ROWS == 0
          and lanes % G == 0 and (lanes // G) % _LANES == 0)
    if force_xla or not ok:
        return ssd_step_reference(state, layer, x, dt, A, Bm, Cm, live)
    return platform_dispatch(_step_pallas, ssd_step_reference,
                             state, layer, x, dt, A, Bm, Cm, live)
