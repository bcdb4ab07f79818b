"""Selective state-space scan (Mamba-1) and its one-token decode form.

    S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) B_t^T        S [N, Di]
    y_t = C_t^T S_t + D * u_t

State and arithmetic are float32 whatever the activations' type. The state
is laid out [N, Di]: the inner width is the lane axis, the 16 state rows sit
on sublanes, so one step is a handful of full vector operations and the
reduction over N is a sublane reduce.

`ssm_scan` runs a sequence (the prefill programs): a Pallas kernel with the
state resident in VMEM over time chunks, initial state in and final state
out; an XLA `lax.scan` elsewhere. `ssm_step` is decode: one token for every
slot, updating ONE layer of the engine's whole state array
[layers, B, N, Di] in place (the layer rides as a scalar prefetch, as in
ops/paged_attention.py: a layer's slab is never sliced out and put back).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret_mode, platform_dispatch, use_pallas

_LANES = 128
_ROWS = 8  # float32 sublanes of a tile: time steps a loop iteration takes


def _lanes(x):
    """[..., N] -> [..., N, 128]: each value repeated along a lane axis, so
    a kernel reads a [N, 1] column of it without a lane-to-sublane move."""
    return jnp.broadcast_to(x[..., None].astype(jnp.float32),
                            (*x.shape, _LANES))


def _one_step(s, u_t, dt_t, b, c, A, D):
    """s [N, d]; u_t, dt_t, D [1, d]; b, c [N, 1]; A [N, d]."""
    s = jnp.exp(dt_t * A) * s + (dt_t * u_t) * b
    return s, jnp.sum(s * c, axis=0, keepdims=True) + D * u_t


# ---------------------------------------------------------------------------
# a sequence
# ---------------------------------------------------------------------------


def ssm_scan_reference(u, dt, A, Bm, Cm, D, s0):
    """u, dt [B,T,Di]; A [N,Di]; Bm, Cm [B,T,N]; D [Di]; s0 [B,N,Di] f32
    -> (y [B,T,Di] f32, s1 [B,N,Di] f32)."""
    f32 = jnp.float32
    A, D = A.astype(f32), D.astype(f32)

    def step(s, xs):
        u_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None, :] * A) * s \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.einsum("bnd,bn->bd", s, c_t) + D * u_t

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (u, dt, Bm, Cm))
    s1, y = jax.lax.scan(step, s0.astype(f32), xs)
    return jnp.moveaxis(y, 0, 1), s1


def _scan_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
                 y_ref, s1_ref, s_scr, *, chunk, n_chunks):
    tc = pl.program_id(2)

    @pl.when(tc == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    A, D = a_ref[...], d_ref[...]

    def rows(g, s):
        base = pl.multiple_of(g * _ROWS, _ROWS)
        u8 = u_ref[0, pl.ds(base, _ROWS), :]
        dt8 = dt_ref[0, pl.ds(base, _ROWS), :]
        ys = []
        for i in range(_ROWS):
            s, y = _one_step(s, u8[i:i + 1], dt8[i:i + 1],
                             b_ref[0, base + i][:, :1],
                             c_ref[0, base + i][:, :1], A, D)
            ys.append(y)
        y_ref[0, pl.ds(base, _ROWS), :] = jnp.concatenate(ys, axis=0)
        return s

    s = jax.lax.fori_loop(0, chunk // _ROWS, rows, s_scr[...])
    s_scr[...] = s

    @pl.when(tc == n_chunks - 1)
    def _finish():
        s1_ref[0] = s


def _blocks(T: int, Di: int):
    chunk = next(c for c in (128, 64, 32, 16, 8) if T % c == 0)
    return chunk, 512 if Di % 512 == 0 else _LANES


def _scan_pallas(u, dt, A, Bm, Cm, D, s0):
    B, T, Di = u.shape
    N = A.shape[0]
    f32 = jnp.float32
    chunk, dblk = _blocks(T, Di)
    n_chunks = T // chunk
    seq = pl.BlockSpec((1, chunk, dblk), lambda b, d, t: (b, t, d))
    col = pl.BlockSpec((1, chunk, N, _LANES), lambda b, d, t: (b, t, 0, 0))
    state = pl.BlockSpec((1, N, dblk), lambda b, d, t: (b, 0, d))
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=(B, Di // dblk, n_chunks),
        in_specs=[seq, seq,
                  pl.BlockSpec((N, dblk), lambda b, d, t: (0, d)),
                  col, col,
                  pl.BlockSpec((1, dblk), lambda b, d, t: (0, d)),
                  state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((B, T, Di), f32),
                   jax.ShapeDtypeStruct((B, N, Di), f32)],
        scratch_shapes=[pltpu.VMEM((N, dblk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_scan",
        interpret=interpret_mode(),
    )(u.astype(f32), dt.astype(f32), A.astype(f32), _lanes(Bm), _lanes(Cm),
      D.astype(f32).reshape(1, Di), s0.astype(f32))


def ssm_scan(u, dt, A, Bm, Cm, D, s0, force_xla: bool = False):
    """The scan of one layer over a sequence, from state s0.

    u [B,T,Di] (after the conv and its silu), dt [B,T,Di] (after softplus;
    0 at a position leaves the state as it was, which is how padding is
    passed over), A [N,Di] (negative), Bm / Cm [B,T,N], D [Di],
    s0 [B,N,Di]. -> (y [B,T,Di] float32, final state [B,N,Di] float32)."""
    _, T, Di = u.shape
    ok = use_pallas() and T % _ROWS == 0 and Di % _LANES == 0
    if force_xla or not ok:
        return ssm_scan_reference(u, dt, A, Bm, Cm, D, s0)
    return platform_dispatch(_scan_pallas, ssm_scan_reference,
                             u, dt, A, Bm, Cm, D, s0)


# ---------------------------------------------------------------------------
# one token for every slot
# ---------------------------------------------------------------------------


def ssm_step_reference(state, layer, u, dt, A, Bm, Cm, D):
    """state [L,B,N,Di] f32; u, dt [B,Di]; Bm, Cm [B,N]
    -> (y [B,Di] f32, state with layer `layer` advanced)."""
    f32 = jnp.float32
    y, s1 = ssm_scan_reference(u[:, None], dt[:, None], A, Bm[:, None],
                               Cm[:, None], D, state[layer].astype(f32))
    return y[:, 0], jax.lax.dynamic_update_index_in_dim(
        state, s1.astype(state.dtype), layer, 0)


def _step_kernel(layer_ref, u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s_ref,
                 y_ref, so_ref, *, rows):
    del layer_ref  # the block specs read it
    A, D = a_ref[...], d_ref[...]
    u, dt = u_ref[...], dt_ref[...]
    ys = []
    for i in range(rows):
        s, y = _one_step(s_ref[0, i], u[i:i + 1], dt[i:i + 1],
                         b_ref[i][:, :1], c_ref[i][:, :1], A, D)
        so_ref[0, i] = s
        ys.append(y)
    y_ref[...] = jnp.concatenate(ys, axis=0)


def _step_pallas(state, layer, u, dt, A, Bm, Cm, D):
    _, B, N, Di = state.shape
    f32 = jnp.float32
    rows = _ROWS
    dblk = 512 if Di % 512 == 0 else _LANES
    row = pl.BlockSpec((rows, dblk), lambda b, d, l: (b, d))
    col = pl.BlockSpec((rows, N, _LANES), lambda b, d, l: (b, 0, 0))
    slab = pl.BlockSpec((1, rows, N, dblk), lambda b, d, l: (l[0], b, 0, d))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // rows, Di // dblk),
            in_specs=[row, row,
                      pl.BlockSpec((N, dblk), lambda b, d, l: (0, d)),
                      col, col,
                      pl.BlockSpec((1, dblk), lambda b, d, l: (0, d)),
                      slab],
            out_specs=[row, slab],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Di), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operand 7 (the scalar prefetch counts) is the state: in place
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="ssm_step",
        interpret=interpret_mode(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), u.astype(f32),
      dt.astype(f32), A.astype(f32), _lanes(Bm), _lanes(Cm),
      D.astype(f32).reshape(1, Di), state)
    return y, state


def ssm_step(state, layer, u, dt, A, Bm, Cm, D, force_xla: bool = False):
    """Decode: advance layer `layer` of the whole state [L,B,N,Di] (float32)
    by one token per slot, in place. u, dt [B,Di]; Bm, Cm [B,N].
    -> (y [B,Di] float32, state). One pass over that layer's state."""
    _, B, _, Di = state.shape
    ok = (use_pallas() and B % _ROWS == 0 and Di % _LANES == 0
          and state.dtype == jnp.float32)
    if force_xla or not ok:
        return ssm_step_reference(state, layer, u, dt, A, Bm, Cm, D)
    return platform_dispatch(_step_pallas, ssm_step_reference,
                             state, layer, u, dt, A, Bm, Cm, D)
