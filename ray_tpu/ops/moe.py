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

A program of MANY tokens (a prefill chunk, a bucket) is the other end: 256
rows touch every expert, and an expert run over all of them multiplies
E / k times the rows that chose it. `expert_groups` visits each chosen
expert once as a step does, streams its blocks once, and multiplies the
rows that chose it and no others: the rows are picked out of x, which lies
whole in VMEM, by a one-hot product (exact: one 1 a row) in passes of at
most `_PASS` rows, and put back the same way before the float32 weight of
the choice. A row that holds no token joins no group.
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
# rows of one pass over an expert's blocks: up to the MXU's 128 a weight
# tile's load hides the rows streamed through it, so a pass costs what the
# tile loads cost, however few rows it holds
_PASS = 128
# what a call may take of VMEM (v5e: 128 MiB)
_VMEM_BYTES = 100 * 2 ** 20


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


def expert_step_reference(act, x, c, w_in, w_gate, w_out, layer, *lists):
    """The XLA form: every expert of layer `layer` over the rows, and the
    combine's zeros for what a kernel would not visit or multiply."""
    del lists  # a kernel's tables: c holds zeros where they end
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


def _select(onehot, rows):
    """onehot [M, K] (one 1 a row at the most) . rows [K, D] -> float32:
    the rows picked out, exactly (a float32 product in one bfloat16 pass
    would round them)."""
    precision = (jax.lax.Precision.DEFAULT if rows.dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    return jnp.dot(onehot, rows, precision=precision,
                   preferred_element_type=_F32)


def _column(table, e):
    """table [N, E], e a scalar -> its column e [N, 1]."""
    mine = jax.lax.broadcasted_iota(jnp.int32, table.shape, 1) == e
    return jnp.sum(jnp.where(mine, table, 0), axis=1, keepdims=True)


def _gated_part(x, in_ref, gate_ref, out_w_ref, act):
    """One expert's gated FFN over the rows x, a tile of the expert width:
    the XLA form's rounding points, with float32 between them (the vector
    unit has no bfloat16 logistic) -> the down product's part, float32."""
    dtype = x.dtype
    h = _product(x, in_ref[...]).astype(dtype)
    g = _product(x, gate_ref[...]).astype(dtype)
    a = act(g.astype(_F32)).astype(dtype).astype(_F32) * h.astype(_F32)
    return _product(a.astype(dtype), out_w_ref[...])


def _step_kernel(layer_ref, order_ref, count_ref, x_ref, c_ref, in_ref,
                 gate_ref, out_w_ref, o_ref, acc_ref, *, act, tiles):
    del layer_ref  # the block specs read it
    j, f = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (f == 0))
    def _start():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < count_ref[0])
    def _visit():
        part = _gated_part(x_ref[...], in_ref, gate_ref, out_w_ref, act)

        @pl.when(f == 0)
        def _first():
            acc_ref[...] = part

        @pl.when(f > 0)
        def _more():
            acc_ref[...] += part

        @pl.when(f == tiles - 1)
        def _combine():
            # the expert's down product rounded once, then its column of
            # the combine matrix in float32 (op for op the kernel PR 42
            # measured: the profiler's fingerprint of a decode program covers
            # a kernel's operations in their order, not where they are
            # written)
            c = c_ref[...]
            mine = jax.lax.broadcasted_iota(
                jnp.int32, c.shape, 1) == order_ref[j]
            col = jnp.sum(jnp.where(mine, c, 0.0), axis=1, keepdims=True)
            o_ref[...] += (acc_ref[...].astype(x_ref.dtype).astype(_F32)
                           * col)


def _groups_kernel(layer_ref, order_ref, count_ref, held_ref, x_ref, c_ref,
                   place_ref, slots_ref, in_ref, gate_ref, out_w_ref, o_ref,
                   xg_ref, acc_ref, *, act, tiles, rows):
    del layer_ref  # the block specs read it
    j, f = pl.program_id(0), pl.program_id(1)
    N, dtype = x_ref.shape[0], x_ref.dtype

    @pl.when((j == 0) & (f == 0))
    def _start():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < count_ref[0])
    def _visit():
        e = order_ref[j]

        def one_pass(p, carry):
            first = pl.multiple_of(p * rows, rows)
            span = pl.ds(first, rows)

            @pl.when(f == 0)
            def _gather():
                # slot i of the pass takes the row whose place in the
                # group is first + i (a row outside the group: place -1)
                pick = jax.lax.broadcasted_iota(
                    jnp.int32, (rows, N), 0) == slots_ref[...] - first
                xg_ref[span, :] = _select(
                    pick.astype(dtype), x_ref[...]).astype(dtype)

            part = _gated_part(xg_ref[span, :], in_ref, gate_ref, out_w_ref,
                               act)

            @pl.when(f == 0)
            def _first():
                acc_ref[span, :] = part

            @pl.when(f > 0)
            def _more():
                acc_ref[span, :] += part

            @pl.when(f == tiles - 1)
            def _combine():
                # the down product rounded once, put back at its rows, then
                # the float32 weight of the choice
                y = acc_ref[span, :].astype(dtype)
                put = jax.lax.broadcasted_iota(
                    jnp.int32, (N, rows), 1) == _column(place_ref[...],
                                                        e) - first
                o_ref[...] += (_select(put.astype(dtype), y)
                               * _column(c_ref[...], e))

            return carry

        jax.lax.fori_loop(0, pl.cdiv(held_ref[e], rows), one_pass, 0)


def f_tile(D: int, F: int, itemsize: int, block_bytes: int = _BLOCK_BYTES):
    """The tile of the expert width a block takes: the most whole 128-lane
    tiles that divide F and keep a [D, tile] block within `block_bytes`."""
    n = F // _LANES
    fits = [t for t in range(1, n + 1)
            if n % t == 0 and D * t * _LANES * itemsize <= block_bytes]
    return _LANES * max(fits, default=1)


def _weight_blocks(D: int, tf: int, tiles: int):
    """The blocks of w_in, w_gate [layers, E, D, F] and w_out for a grid of
    (visit j, tile f) whose first three scalar prefetches are the layer, the
    list of experts to visit and its length."""

    def at(j, f, layer, order, count, *_):
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

    return [pl.BlockSpec((None, None, D, tf), up),
            pl.BlockSpec((None, None, D, tf), up),
            pl.BlockSpec((None, None, tf, D), down)]


def _whole(shape):
    return pl.BlockSpec(shape, lambda j, f, *_: (0,) * len(shape))


def _step_pallas(act, x, c, w_in, w_gate, w_out, layer, order, count):
    N, D = x.shape
    _, E, _, F = w_in.shape
    size = w_in.dtype.itemsize
    tf = f_tile(D, F, size)
    tiles = F // tf
    need = (6 * D * tf * size + N * D * (x.dtype.itemsize + 12)
            + 16 * N * tf + 2 * N * max(E, _LANES) * 4)
    return pl.pallas_call(
        functools.partial(_step_kernel, act=act, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(E, tiles),
            in_specs=[_whole((N, D)), _whole((N, E)),
                      *_weight_blocks(D, tf, tiles)],
            out_specs=_whole((N, D)),
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


def _groups_need(N: int, D: int, E: int, tf: int, itemsize: int,
                 size: int) -> int:
    """VMEM bytes of a `moe_groups` call: three weight blocks twice, x and
    the float32 output twice, the gathered rows, their float32 accumulator
    and one pass's rows put back, and the tables."""
    return (6 * D * tf * size + N * D * (3 * itemsize + 16)
            + 2 * _PASS * D * 4 + 16 * _PASS * tf
            + 5 * N * max(E, _LANES) * 4)


def groups_fit(N: int, D: int, E: int, F: int, itemsize: int) -> bool:
    """Whether `expert_groups` holds a program's N rows of width D whole in
    VMEM beside an expert's blocks (x, the output and the accumulator all
    lie there): 512 rows at the four published shapes, 1024 at the two
    narrowest (2048 and 2560 wide). Past it the XLA form runs; no cell's
    traffic does (a bucket's rows are 64 to 256, a chunk's 256, or 512
    where the engine asks this function whether its wide chunk fits,
    serve/engine.py `_wide_chunk`; measured to 512, PERF.md section 6,
    PR 43 and PR 46)."""
    tf = f_tile(D, F, itemsize)
    return _groups_need(N, D, E, tf, itemsize, itemsize) <= _VMEM_BYTES


def _groups_pallas(act, x, c, w_in, w_gate, w_out, layer, order, count,
                   held, place):
    N, D = x.shape
    _, E, _, F = w_in.shape
    size = w_in.dtype.itemsize
    tf = f_tile(D, F, size)
    tiles = F // tf
    rows = min(_PASS, N)
    cap = -(-N // rows) * rows

    def slots(j, f, layer, order, count, held):
        return order[jnp.minimum(j, jnp.maximum(count[0] - 1, 0))], 0, 0

    need = _groups_need(N, D, E, tf, x.dtype.itemsize, size)
    return pl.pallas_call(
        functools.partial(_groups_kernel, act=act, tiles=tiles, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(E, tiles),
            in_specs=[_whole((N, D)), _whole((N, E)), _whole((N, E)),
                      pl.BlockSpec((None, 1, N), slots),
                      *_weight_blocks(D, tf, tiles)],
            out_specs=_whole((N, D)),
            scratch_shapes=[pltpu.VMEM((cap, D), x.dtype),
                            pltpu.VMEM((cap, D), _F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((N, D), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(need + 16 * 2 ** 20, 120 * 2 ** 20)),
        name="moe_groups",
        interpret=interpret_mode(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, count.reshape(1),
      held, x, c, place, place.T[:, None, :], w_in, w_gate, w_out)


def _tiles(x, w_in) -> bool:
    """Whether the shapes tile: whole 128-lane tiles of D and F, whole
    sublane tiles of rows."""
    N, D = x.shape
    F = w_in.shape[-1]
    return (D % _LANES == 0 and F % _LANES == 0
            and N % (8 * 4 // x.dtype.itemsize) == 0)


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
    order, count = visit_list(hit)
    c = jnp.where(hit[None, :], c, 0.0)
    args = (x, c, w_in, w_gate, w_out, jnp.asarray(layer, jnp.int32), order,
            count)
    if force_xla or not (use_pallas() and _tiles(x, w_in)):
        return expert_step_reference(act, *args), count
    return _dispatched(_step_pallas, act, _forced(), *args), count


def expert_groups(x, c, member, w_in, w_gate, w_out, layer, act,
                  force_xla: bool = False):
    """`expert_step` for a program of many tokens: member bool [N, E], the
    rows that chose each expert (a row that holds no token is no member of
    any group); an expert with a member is visited, and runs over its
    members alone. -> out [N, D] float32: row n's weighted sum over the
    experts it is a member of (zero for a row of no group).

    The kernel's rows are its passes': an expert of m members costs
    ceil(m / 128) passes of 128 rows (`groups_rows_bound`). Kernel alone
    against the XLA form, ms a layer on one TPU v5e: see PERF.md section 6,
    PR 43."""
    held = jnp.sum(member, axis=0, dtype=jnp.int32)
    order, count = visit_list(held > 0)
    c = jnp.where(member, c, 0.0)
    # a member's place in its group, in the rows' order; -1 outside it
    place = jnp.where(member, jnp.cumsum(member, axis=0, dtype=jnp.int32) - 1,
                      -1)
    args = (x, c, w_in, w_gate, w_out, jnp.asarray(layer, jnp.int32), order,
            count, held, place)
    fits = groups_fit(*x.shape, w_in.shape[1], w_in.shape[-1],
                      x.dtype.itemsize)
    if force_xla or not (use_pallas() and _tiles(x, w_in) and fits):
        return expert_step_reference(act, *args)
    return _dispatched(_groups_pallas, act, _forced(), *args)


def groups_rows_bound(N: int, E: int, k: int, tokens: int) -> int:
    """The most rows `expert_groups`' passes cover for a program of N rows
    that hold `tokens` tokens of k choices each among E experts: every
    choice once, and at the most a pass less one row of padding for every
    expert visited; never more than every visited expert over every pass.
    A BOUND from the static shape (the device alone knows the groups)."""
    rows = min(_PASS, N)
    visited = min(E, k * tokens)
    return min(k * tokens + visited * (rows - 1),
               visited * -(-tokens // rows) * rows)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _dispatched(kernel, act, forced, *args):
    """One function a program, however many layers call it at one shape: a
    period's layers share the kernel's lowering (a fifth of a second each,
    in every decode program a replica warms). `kernel`: `_step_pallas` or
    `_groups_pallas`, whose XLA form is one (the groups' tables are the
    kernel's alone); `forced`: what the environment asks of the dispatch,
    which this trace is cached under."""
    del forced
    return platform_dispatch(
        functools.partial(kernel, act),
        functools.partial(expert_step_reference, act), *args)
