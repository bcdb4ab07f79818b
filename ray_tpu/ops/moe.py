"""The expert product of a decode STEP: the experts that a live row chose,
and no byte of the others.

    out[n] = sum over visited e of c[n, e] * round(expert_e(x_n))
    expert_e(x) = (act(x W_gate[e]) * (x W_in[e])) W_out[e]

A step is a few rows (one token a decode slot) against every expert's
weights, so its time is the weights' bytes. XLA cannot leave a slice of an
operand unread by data; a kernel can: the compacted list of the experts to
visit and its length are scalar-prefetched, the weight blocks' index maps
read the list, and past its end they name the block fetched last, so the
pipeline starts no copy and `pl.when` skips the body. Every visited expert
runs over ALL the rows (the step is bound by memory; rows are free, and
there is no gather or scatter of rows), and the float32 combine column
c[:, e] weights its rounded result, zero for the rows that did not choose
it: the sum `models/transformer.py _moe_ffn_dropless_ids` computes over
every expert, with the terms left out that are zero for every live row.

The weights are read WHERE THEY LIE: the op takes a segment's whole stack
[layers, E, D, F] and the layer as a scalar, as the paged kernels take
their pool (a slice of the stack before a custom call is a copy of the
layer's experts every step). A block is the whole model width by a tile of
the expert width: some MiB, one strided copy of long runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import _forced, interpret_mode, platform_dispatch, use_pallas

_LANES = 128
_F32 = jnp.float32
# what one weight block may take of VMEM (three of them, each twice)
_BLOCK_BYTES = 4 * 2 ** 20


def visit_list(hit):
    """hit bool [E] -> (order int32 [E], count int32 []): the experts to
    visit, in their own order, in order[:count] (zeros after). No sort and
    no scatter: entry j is the expert with j hits before it."""
    E = hit.shape[0]
    idx = jnp.arange(E, dtype=jnp.int32)
    before = jnp.cumsum(hit, dtype=jnp.int32) - 1
    pick = hit[None, :] & (before[None, :] == idx[:, None])
    return (jnp.sum(jnp.where(pick, idx[None, :], 0), axis=1, dtype=jnp.int32),
            jnp.sum(hit, dtype=jnp.int32))


def expert_step_reference(act, x, c, w_in, w_gate, w_out, layer, order,
                          count):
    """The XLA form: every expert of layer `layer` over the rows, and the
    combine's zeros for what the kernel would not visit."""
    del order, count  # c holds zeros where they end
    dtype = x.dtype
    w_in, w_gate, w_out = (
        jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False).astype(dtype)
        for w in (w_in, w_gate, w_out))
    h = jnp.einsum("nd,edf->enf", x, w_in)
    g = jnp.einsum("nd,edf->enf", x, w_gate)
    y = jnp.einsum("enf,efd->end", act(g) * h, w_out)
    return jnp.sum(y.astype(_F32) * c.T[:, :, None], axis=0)


def _product(a, w):
    """a [rows, K] . w [K, M] (cast to a's type) -> float32. Two bfloat16
    operands go to the MXU as they are, whatever the process's default
    precision asks of float32 products."""
    precision = (jax.lax.Precision.DEFAULT if a.dtype == jnp.bfloat16
                 else None)
    return jnp.dot(a, w.astype(a.dtype), precision=precision,
                   preferred_element_type=_F32)


def _step_kernel(layer_ref, order_ref, count_ref, x_ref, c_ref, in_ref,
                 gate_ref, out_w_ref, o_ref, acc_ref, *, act, tiles):
    del layer_ref  # the block specs read it
    j, f = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (f == 0))
    def _start():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < count_ref[0])
    def _visit():
        x = x_ref[...]
        dtype = x.dtype
        h = _product(x, in_ref[...]).astype(dtype)
        g = _product(x, gate_ref[...]).astype(dtype)
        # the XLA form's rounding points, with float32 between them (the
        # vector unit has no bfloat16 logistic)
        a = act(g.astype(_F32)).astype(dtype).astype(_F32) * h.astype(_F32)
        part = _product(a.astype(dtype), out_w_ref[...])

        @pl.when(f == 0)
        def _first():
            acc_ref[...] = part

        @pl.when(f > 0)
        def _more():
            acc_ref[...] += part

        @pl.when(f == tiles - 1)
        def _combine():
            # the expert's down product rounded once, then its column of
            # the combine matrix in float32
            c = c_ref[...]
            mine = jax.lax.broadcasted_iota(
                jnp.int32, c.shape, 1) == order_ref[j]
            col = jnp.sum(jnp.where(mine, c, 0.0), axis=1, keepdims=True)
            o_ref[...] += acc_ref[...].astype(dtype).astype(_F32) * col


def f_tile(D: int, F: int, itemsize: int, block_bytes: int = _BLOCK_BYTES):
    """The tile of the expert width a block takes: the most whole 128-lane
    tiles that divide F and keep a [D, tile] block within `block_bytes`."""
    n = F // _LANES
    fits = [t for t in range(1, n + 1)
            if n % t == 0 and D * t * _LANES * itemsize <= block_bytes]
    return _LANES * max(fits, default=1)


def _step_pallas(act, x, c, w_in, w_gate, w_out, layer, order, count):
    N, D = x.shape
    _, E, _, F = w_in.shape
    size = w_in.dtype.itemsize
    tf = f_tile(D, F, size)
    tiles = F // tf

    def at(j, f, layer, order, count):
        # past the list's end: the block the last visit ended on, which is
        # in VMEM already (nothing is visited: block 0 of expert 0)
        last = jnp.maximum(count[0] - 1, 0)
        return (layer[0], order[jnp.minimum(j, last)],
                jnp.where(j < count[0], f, tiles - 1))

    def up(j, f, *scalars):
        layer, e, t = at(j, f, *scalars)
        return layer, e, 0, t

    def down(j, f, *scalars):
        layer, e, t = at(j, f, *scalars)
        return layer, e, t, 0

    whole = lambda j, f, *_: (0, 0)  # noqa: E731
    need = (6 * D * tf * size + N * D * (x.dtype.itemsize + 12)
            + 16 * N * tf + 2 * N * max(E, _LANES) * 4)
    return pl.pallas_call(
        functools.partial(_step_kernel, act=act, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, tiles),
            in_specs=[pl.BlockSpec((N, D), whole),
                      pl.BlockSpec((N, E), whole),
                      pl.BlockSpec((None, None, D, tf), up),
                      pl.BlockSpec((None, None, D, tf), up),
                      pl.BlockSpec((None, None, tf, D), down)],
            out_specs=pl.BlockSpec((N, D), whole),
            scratch_shapes=[pltpu.VMEM((N, D), _F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N, D), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(need + 16 * 2 ** 20, 110 * 2 ** 20)),
        name="moe_step",
        interpret=interpret_mode(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, count.reshape(1),
      x, c, w_in, w_gate, w_out)


def expert_step(x, c, hit, w_in, w_gate, w_out, layer, act,
                force_xla: bool = False):
    """x [N, D] (the activations' type); c [N, E] float32, a row's weights
    at its experts; hit bool [E], the experts to visit; w_in, w_gate
    [layers, E, D, F] and w_out [layers, E, F, D], the stacks of a segment,
    of which layer `layer` (a scalar) is read; act: the gate's activation.
    -> (out [N, D] float32: the weighted sum over the visited experts, zero
    where none is; visited int32 []: how many were).

    The Pallas kernel on the TPU where the shapes tile (whole 128-lane
    tiles of D and F, whole sublane tiles of rows); the XLA form, every
    expert's product times the combine's zeros, everywhere else."""
    N, D = x.shape
    F = w_in.shape[-1]
    order, count = visit_list(hit)
    c = jnp.where(hit[None, :], c, 0.0)
    rows = 8 * 4 // x.dtype.itemsize
    ok = (use_pallas() and D % _LANES == 0 and F % _LANES == 0
          and N % rows == 0)
    args = (x, c, w_in, w_gate, w_out, jnp.asarray(layer, jnp.int32), order,
            count)
    if force_xla or not ok:
        return expert_step_reference(act, *args), count
    return _dispatched(act, _forced(), *args), count


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dispatched(act, forced, *args):
    """One function a program, however many layers call it at one shape: a
    period's layers share the kernel's lowering (a fifth of a second each,
    in every decode program a replica warms). `forced`: what the
    environment asks of the dispatch, which this trace is cached under."""
    del forced
    return platform_dispatch(
        functools.partial(_step_pallas, act),
        functools.partial(expert_step_reference, act), *args)
