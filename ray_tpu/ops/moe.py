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
from jax.ad_checkpoint import checkpoint_name
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


def _mxu_precision(dtype):
    """Two bfloat16 operands go to the MXU as they are, whatever the
    process's default precision asks of float32 products."""
    return jax.lax.Precision.DEFAULT if dtype == jnp.bfloat16 else None


def _product(a, w):
    """a [rows, K] . w [K, M] (cast to a's type) -> float32."""
    return jnp.dot(a, w.astype(a.dtype), precision=_mxu_precision(a.dtype),
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


# ---------------------------------------------------------------------------
# Training rows: a grouped product that has a backward
# ---------------------------------------------------------------------------
#
# A training step is the other end again: tens of thousands of rows, every
# held expert chosen by thousands, and a backward. x cannot lie in VMEM, so
# the rows are SORTED by expert into a buffer in which every expert's group
# starts on a tile of `tile` rows (`group_rows`): a tile belongs to ONE
# expert, and the grouped product is a tiled matrix product whose weight
# block is picked by the tile's expert (scalar-prefetched). Consecutive
# tiles of an expert name the same block, which the pipeline does not fetch
# again, so an expert's weights are read once a product. Three kernels:
#
#   moe_gmm     out[tile] = x[tile] . w[expert(tile)]        (forward, x3)
#   moe_gmm_dx  the same with w transposed: the rows' gradient (x3)
#   moe_gmm_dw  dw[e] = sum over e's tiles of x[tile]^T . dy[tile] (x3)
#
# The sort is a partial permutation and `group_rows` gives it in both
# directions (`token`: row -> token, `slot`: a token's j-th choice -> row),
# so a row moves by a gather whichever way it goes: into the buffer through
# `token`, and out of it, summed at its token, through `slot`
# (`gather_rows`, kernel `moe_combine`): no row is scatter-added, forward or
# backward.
#
# The buffer's size is static (`grouped_rows_bound`); the tiles past the
# used ones are skipped (no block fetched, zeros written), and a routing
# that would pass the bound is for the caller to fail on: nothing here
# drops a row silently (`group_rows` says how many rows the routing needs).

# names for a `jax.checkpoint` policy to save by (models/stack.py): the two
# up products, which `grouped_ffn`'s forward rule names, and the layer's
# combined result, which models/transformer.py `_moe_ffn_grouped` names
GROUPED_RESIDUAL_NAMES = ("moe_up", "moe_gate", "moe_out")
# the least rows an expert should expect before its rows are sorted: one
# tile of the smallest size. NOT MEASURED: under a tile an expert every
# group is mostly its own padding, which is all the reason there is; where
# the two forms cross on the chip nobody has read (the one shape measured is
# the train cell's, 2048 rows an expert at tile 512: PERF.md section 5)
GROUPED_MIN_ROWS = 128
_ACC_BYTES = 4 * 2 ** 20


def grouped_tile(rows_per_expert: float) -> int:
    """Rows of a tile of the sorted buffer: large where an expert has
    thousands of rows (a weight block's load hides behind more products),
    small where the padding of a group's last tile would outweigh that.
    The thresholds keep a group's padding (half a tile an expert on
    average) under an eighth of its rows; only 512 at 2048 rows an expert
    has been timed on the chip, the other two are arithmetic."""
    return 512 if rows_per_expert >= 2048 else (
        256 if rows_per_expert >= 512 else 128)


def grouped_rows_bound(N: int, k: int, E: int, W: int, tile: int) -> int:
    """Rows of the sorted buffer for N tokens of k choices each among W
    experts of which E are held: every choice that can fall on a held
    expert where all are held (nothing can pass it); TWICE the held
    experts' even share where the layer holds a share (a routing that
    piles more than that on this chip's experts fails, loudly); and a tile
    a held expert for its group's padding (an expert nobody chose keeps
    one tile of zeros: its weights' gradient is written, as zeros). Never
    more than every token choosing min(k, E) held experts. Whole tiles."""
    worst = N * min(k, E)
    rows = worst if E == W else min(worst, 2 * -(-N * k * E // W))
    return (-(-rows // tile) + E) * tile


def group_rows(expert_ids, weights, first: int, E: int, tile: int,
               bound: int):
    """expert_ids [N,k] int32 over all the router's outputs, weights [N,k]
    float32; the held experts are first .. first + E. -> the sorted
    buffer's tables, a partial permutation in BOTH directions: `token`
    [bound] (the row's token; N: the row is padding; `choice`: which of
    the N * k choices, n * k + j, it is; no two rows name one, a padding
    row names none: N * k + its own number) and its inverse `slot`
    [N,k] (the row of token n's j-th choice; `bound`, the fill row, where
    that expert is not held here or the row lies past the bound), so that a
    row's way into the buffer and back is a gather either way
    (`gather_rows`, which also reads `runs` [tiles of tokens + 1, E]: the
    row where each held expert's run of a tile's tokens starts, the sort
    keeping a group's tokens in order); `weight` [bound] float32 (0 for
    padding), `tile_expert` [bound / tile] (past the used tiles: the last
    expert), `used` [1] (the tiles that hold a group) and `rows` (the rows
    the routing NEEDS, which is more than `bound` where it overflows: the
    rows past the bound are then missing from the tables)."""
    N, k = expert_ids.shape
    ids = expert_ids - first
    ids = jnp.where((ids >= 0) & (ids < E), ids, E).astype(jnp.int32)
    local = ids.reshape(-1)
    # held choices first, expert by expert, a group's tokens in their order
    _, order = jax.lax.sort_key_val(
        local, jnp.arange(N * k, dtype=jnp.int32), is_stable=True)
    # [N,k,E]: choice j of token n is expert e; a token's choices an expert
    chose = ids[:, :, None] == jnp.arange(E, dtype=jnp.int32)
    per_token = jnp.sum(chose, axis=1, dtype=jnp.int32)
    before = jnp.cumsum(per_token, axis=0) - per_token  # tokens BEFORE n
    counts = before[-1] + per_token[-1]
    tiles = jnp.maximum(-(-counts // tile), 1)
    tile_end = jnp.cumsum(tiles)
    used = tile_end[-1]
    sorted_start = jnp.cumsum(counts) - counts
    group_start = (tile_end - tiles) * tile
    n_tiles = bound // tile
    t = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.sum(t[:, None] >= tile_end[None, :], axis=1, dtype=jnp.int32),
        E - 1)
    row = jnp.arange(bound, dtype=jnp.int32)
    e = tile_expert[row // tile]
    at = row - group_start[e]
    valid = (at < counts[e]) & (row // tile < used)
    choice = order[jnp.clip(sorted_start[e] + at, 0, N * k - 1)]
    # the inverse without a second sort: a choice's place in its group is
    # how many choices before it (earlier tokens, then earlier choices of
    # its own token) fell on its expert, what the stable sort counts too
    earlier = jnp.sum(
        (ids[:, :, None] == ids[:, None, :])
        & (jnp.arange(k)[:, None] > jnp.arange(k)[None, :]), axis=2,
        dtype=jnp.int32)
    slot = jnp.sum(jnp.where(chose, (group_start + before)[:, None, :], 0),
                   axis=2, dtype=jnp.int32) + earlier
    return {"token": jnp.where(valid, choice // k, N),
            "choice": jnp.where(valid, choice, N * k + row),
            "slot": jnp.where((ids < E) & (slot < bound), slot, bound),
            "runs": group_start + jnp.concatenate(
                [before[::_COMBINE_TOKENS], counts[None]]),
            "weight": jnp.where(valid, weights.reshape(-1)[choice], 0.0),
            "tile_expert": tile_expert,
            "used": jnp.minimum(used, n_tiles).reshape(1),
            "rows": used * tile}


def _n_tile(K: int, N: int, itemsize: int, limit: int) -> int:
    """The widest tile of whole 128 lanes that divides N and keeps a
    [K, tile] block within `limit` bytes."""
    n = N // _LANES
    fits = [t for t in range(1, n + 1)
            if n % t == 0 and K * t * _LANES * itemsize <= limit]
    return _LANES * max(fits, default=1)


def grouped_fits(D: int, F: int, itemsize: int, tile: int) -> bool:
    """Whether the three kernels' blocks lie in VMEM at these widths: a
    tile of rows of the wider of D and F (twice), a weight block and the
    float32 accumulator of the weights' gradient."""
    K = max(D, F)
    return (4 * tile * K * itemsize + 2 * _BLOCK_BYTES + 3 * _ACC_BYTES
            + 8 * tile * _LANES * 8) <= _VMEM_BYTES


def _gmm_kernel(te_ref, used_ref, x_ref, w_ref, o_ref, *, transpose):
    del te_ref  # the block specs read it
    t = pl.program_id(1)

    @pl.when(t < used_ref[0])
    def _product():
        w = w_ref[...]
        dims = (((1,), (1 if transpose else 0,)), ((), ()))
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w.astype(x_ref.dtype), dims,
            precision=_mxu_precision(x_ref.dtype),
            preferred_element_type=_F32).astype(o_ref.dtype)

    @pl.when(t >= used_ref[0])
    def _unused():
        o_ref[...] = jnp.zeros_like(o_ref)


def _last_used(t, used):
    return jnp.minimum(t, used[0] - 1)


def _gmm_pallas(x, w, tile_expert, used, *, tile, transpose, name):
    R, K = x.shape
    N = w.shape[1] if transpose else w.shape[2]
    tn = _n_tile(K, N, w.dtype.itemsize, _BLOCK_BYTES)
    if transpose:
        w_spec = pl.BlockSpec(
            (None, tn, K), lambda n, t, te, u: (te[_last_used(t, u)], n, 0))
    else:
        w_spec = pl.BlockSpec(
            (None, K, tn), lambda n, t, te, u: (te[_last_used(t, u)], 0, n))
    need = (2 * tile * K * x.dtype.itemsize + 2 * K * tn * w.dtype.itemsize
            + tile * tn * (2 * x.dtype.itemsize + 8))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose=transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, R // tile),
            in_specs=[pl.BlockSpec((tile, K), lambda n, t, te, u: (
                _last_used(t, u), 0)), w_spec],
            out_specs=pl.BlockSpec((tile, tn), lambda n, t, te, u: (t, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((R, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(need + 16 * 2 ** 20, 110 * 2 ** 20)),
        name=name,
        interpret=interpret_mode(),
    )(tile_expert, used, x, w)


def _gmm_xla(x, w, tile_expert, used, *, tile, transpose, name):
    del name
    R, K = x.shape
    tiles = R // tile
    picked = w[tile_expert].astype(x.dtype)  # [tiles, K, N] (or [.., N, K])
    out = jnp.einsum("tmk,tnk->tmn" if transpose else "tmk,tkn->tmn",
                     x.reshape(tiles, tile, K), picked,
                     preferred_element_type=_F32)
    live = jnp.arange(tiles)[:, None, None] < used[0]
    return jnp.where(live, out, 0.0).astype(x.dtype).reshape(R, -1)


def _tgmm_kernel(te_ref, used_ref, x_ref, dy_ref, o_ref, acc_ref, *, tiles):
    t, used = pl.program_id(1), used_ref[0]
    e = te_ref[t]
    first = (t == 0) | (te_ref[jnp.maximum(t - 1, 0)] != e)
    last = (t == used - 1) | (te_ref[jnp.minimum(t + 1, tiles - 1)] != e)
    live = t < used

    @pl.when(live & first)
    def _start():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _product():
        # contract over the rows (axis 0 of both): x^T . dy
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            precision=_mxu_precision(x_ref.dtype),
            preferred_element_type=_F32)

    @pl.when(live & last)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tgmm_pallas(x, dy, tile_expert, used, *, tile, experts, dtype, name):
    R, K = x.shape
    N = dy.shape[1]
    tn = _n_tile(K, N, 4, _ACC_BYTES)
    tiles = R // tile
    need = (2 * tile * (K + tn) * x.dtype.itemsize
            + K * tn * (8 + 2 * jnp.dtype(dtype).itemsize))
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, tiles),
            in_specs=[
                pl.BlockSpec((tile, K), lambda n, t, te, u: (
                    _last_used(t, u), 0)),
                pl.BlockSpec((tile, tn), lambda n, t, te, u: (
                    _last_used(t, u), n))],
            out_specs=pl.BlockSpec((None, K, tn), lambda n, t, te, u: (
                te[_last_used(t, u)], 0, n)),
            scratch_shapes=[pltpu.VMEM((K, tn), _F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((experts, K, N), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(need + 16 * 2 ** 20, 110 * 2 ** 20)),
        name=name,
        interpret=interpret_mode(),
    )(tile_expert, used, x, dy)


def _tgmm_xla(x, dy, tile_expert, used, *, tile, experts, dtype, name):
    del name
    R, K = x.shape
    tiles = R // tile
    per_tile = jnp.einsum("tmk,tmn->tkn", x.reshape(tiles, tile, K),
                          dy.reshape(tiles, tile, -1),
                          preferred_element_type=_F32)
    live = jnp.arange(tiles)[:, None, None] < used[0]
    return jax.ops.segment_sum(jnp.where(live, per_tile, 0.0), tile_expert,
                               experts).astype(dtype)


def _grouped_call(pallas, xla, tiled: bool, *args, **static):
    """The kernel where lowering for the TPU and the shapes tile, its XLA
    form (a gather of the tiles' weight blocks: small shapes, tests)
    everywhere else."""
    if not (use_pallas() and tiled):
        return xla(*args, **static)
    return platform_dispatch(functools.partial(pallas, **static),
                             functools.partial(xla, **static), *args)


def _gmm(x, w, tile_expert, used, tile, transpose=False, name="moe_gmm"):
    K, N = (w.shape[2], w.shape[1]) if transpose else w.shape[1:]
    tiled = K % _LANES == 0 and N % _LANES == 0 and tile % 16 == 0
    return _grouped_call(_gmm_pallas, _gmm_xla, tiled, x, w, tile_expert,
                         used, tile=tile, transpose=transpose, name=name)


def _tgmm(x, dy, tile_expert, used, tile, experts, dtype):
    tiled = (x.shape[1] % _LANES == 0 and dy.shape[1] % _LANES == 0
             and tile % 16 == 0)
    return _grouped_call(_tgmm_pallas, _tgmm_xla, tiled, x, dy, tile_expert,
                         used, tile=tile, experts=experts, dtype=dtype,
                         name="moe_gmm_dw")


# `moe_combine`: tokens of one program, rows of one DMA (a whole tile of the
# narrow types), rows of one product (the MXU's side)
_COMBINE_TOKENS = 128
_COMBINE_BLOCK = 16
_COMBINE_CHUNK = 128


def _combine_blocks(k: int, E: int) -> int:
    """The most blocks a tile of tokens can touch: its choices' rows, and
    for every held expert the two blocks its run starts and ends inside;
    whole chunks."""
    per_chunk = _COMBINE_CHUNK // _COMBINE_BLOCK
    blocks = _COMBINE_TOKENS * k // _COMBINE_BLOCK + 2 * E
    return -(-blocks // per_chunk) * per_chunk


def _split3(p):
    """float32 -> three bfloat16 whose sum it is (24 bits of mantissa)."""
    hi = p.astype(jnp.bfloat16)
    rest = p - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


def _combine_kernel(runs_ref, slot_ref, w_ref, y_ref, o_ref, stage_ref,
                    base_ref, staged_ref, acc_ref, sem, *, E, rows,
                    weighted):
    """One tile of tokens. The sort keeps a group's tokens in order, so the
    tile's rows of ONE expert are a run of neighbouring buffer rows
    (`runs_ref`: where each held expert's run starts, tile by tile): the
    blocks of `_COMBINE_BLOCK` rows that cover the runs are copied from the
    buffer in HBM side by side into `stage_ref` (no block of an expert
    that no token of the tile chose, none of the buffer's empty half), and
    a chunk of staged rows is summed at its tokens by ONE product with
    p[n, r] = w[n, j] where slot[n, j] is staged row r's buffer row (a row
    is one choice's: one term an entry at the most, so the product is the
    weighted float32 sum; a float32 weight goes to the MXU as its three
    bfloat16 parts)."""
    i = pl.program_id(0)
    tn, k = slot_ref.shape
    blk, chunk = _COMBINE_BLOCK, _COMBINE_CHUNK
    half = i % 2

    def block_copy(source, at, half):
        return pltpu.make_async_copy(
            y_ref.at[pl.ds(pl.multiple_of(source, blk), blk)],
            stage_ref.at[half, pl.ds(pl.multiple_of(at * blk, blk), blk)],
            sem.at[half])

    def fetch_tile(t, half):
        """Start the copies of tile t's blocks into its half of the stage."""
        def fetch_run(e, staged):
            lo = jnp.minimum(runs_ref[t * E + e], rows)
            hi = jnp.minimum(runs_ref[(t + 1) * E + e], rows)
            first = lo // blk
            n = jnp.where(hi > lo, (hi + blk - 1) // blk - first, 0)

            def fetch(b, carry):
                base_ref[half, staged + b] = (first + b) * blk
                block_copy((first + b) * blk, staged + b, half).start()
                return carry

            jax.lax.fori_loop(0, n, fetch, 0)
            return staged + n

        staged_ref[half] = jax.lax.fori_loop(0, E, fetch_run, 0)

    @pl.when(i == 0)
    def _first():
        # a staged row that no slot names is multiplied by zero: finite
        stage_ref[...] = jnp.zeros_like(stage_ref)
        fetch_tile(0, 0)

    @pl.when(i + 1 < pl.num_programs(0))
    def _ahead():  # the next tile's copies fly under this tile's products
        fetch_tile(i + 1, 1 - half)

    staged = staged_ref[half]

    def landed(_, carry):
        block_copy(0, 0, half).wait()
        return carry

    jax.lax.fori_loop(0, staged, landed, 0)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    slot, w = slot_ref[...], w_ref[...]

    def product(c, carry):
        # the buffer row of each staged row of the chunk; a block past the
        # staged ones names no slot
        start = jnp.full((1, chunk), -chunk, jnp.int32)
        for q in range(chunk // blk):
            b = c * (chunk // blk) + q
            start = jnp.where((lane // blk == q) & (b < staged),
                              base_ref[half, b], start)
        row = start + lane % blk
        p = jnp.zeros((tn, chunk), _F32)
        for j in range(k):
            p = p + jnp.where(slot[:, j:j + 1] == row, w[:, j:j + 1], 0.0)
        staged_rows = stage_ref[half, pl.ds(
            pl.multiple_of(c * chunk, chunk), chunk), :]
        if staged_rows.dtype != jnp.bfloat16:
            parts = (p,)
        else:
            parts = _split3(p) if weighted else (p.astype(jnp.bfloat16),)
        for part in parts:
            acc_ref[...] += _select(part, staged_rows)
        return carry

    jax.lax.fori_loop(0, (staged * blk + chunk - 1) // chunk, product, 0)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _combine_pallas(y, slot, w, runs, *, weighted, dtype, name):
    R, D = y.shape
    N, k = slot.shape
    E = runs.shape[1]
    tn = _COMBINE_TOKENS
    blocks = _combine_blocks(k, E)
    tokens = pl.BlockSpec((tn, k), lambda i, runs: (i, 0))
    need = (2 * blocks * _COMBINE_BLOCK * D * y.dtype.itemsize
            + tn * D * (4 + 4 * jnp.dtype(dtype).itemsize))
    return pl.pallas_call(
        functools.partial(_combine_kernel, E=E, rows=R, weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(N // tn,),
            in_specs=[tokens, tokens, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tn, D), lambda i, runs: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, blocks * _COMBINE_BLOCK, D), y.dtype),
                pltpu.SMEM((2, blocks), jnp.int32),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((tn, D), _F32),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((N, D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(need + 16 * 2 ** 20, 110 * 2 ** 20)),
        name=name,
        interpret=interpret_mode(),
    )(runs.reshape(-1), slot, w, y)


def _combine_xla(y, slot, w, runs, *, weighted, dtype, name):
    del runs, weighted, name
    out = jnp.zeros((slot.shape[0], y.shape[1]), _F32)
    for j in range(slot.shape[1]):  # never [N, k, D] at once
        picked = jnp.take(y, slot[:, j], axis=0, mode="fill", fill_value=0)
        out = out + picked.astype(_F32) * w[:, j:j + 1]
    return out.astype(dtype)


def gather_rows(y, rows, w=None):
    """out[n] = sum over j of w[n, j] * y[slot[n, j]] in float32, rounded
    once to y's type: the rows of the sorted buffer y [R, D] summed at
    their tokens through `group_rows`'s tables `rows` (`slot` [N, k]; a
    slot of R, the fill row, adds nothing); w (float32 [N, k]) left out
    weighs every row 1. What a scatter-add through `token` would add up,
    read from the tokens' side: the experts' results back at their tokens
    (weighted), and the gradient of the gather into the buffer (not).
    y is finite (the kernel multiplies a fetched row that no slot of its
    tile names by zero)."""
    slot, runs = rows["slot"], rows["runs"]
    N, k = slot.shape
    weighted = w is not None
    w = w.astype(_F32) if weighted else jnp.ones((N, k), _F32)
    staged = (2 * _combine_blocks(k, runs.shape[1]) * _COMBINE_BLOCK
              * y.shape[1] * y.dtype.itemsize)
    tiled = (y.shape[1] % _LANES == 0 and N % _COMBINE_TOKENS == 0
             and y.shape[0] % _COMBINE_BLOCK == 0
             and staged <= _VMEM_BYTES // 2 and runs.size <= 2 ** 15)
    return _grouped_call(_combine_pallas, _combine_xla, tiled, y, slot, w,
                         runs, weighted=weighted, dtype=y.dtype,
                         name="moe_combine")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def grouped_ffn(act, tile, xs, w_in, w_gate, w_out, tile_expert, used):
    """The held experts' gated FFN over the sorted buffer xs [R, D] (the
    activations' type; `group_rows` lays it out: tile t of `tile` rows is
    expert `tile_expert[t]`'s, the first `used[0]` tiles hold groups):
    row r -> (act(x_r W_gate[e]) * (x_r W_in[e])) W_out[e], e its tile's
    expert, at the XLA einsums' rounding points (each product rounded to
    the activations' type). w_in, w_gate [E, D, F], w_out [E, F, D].
    -> [R, D]; rows of unused tiles are zero.

    Differentiable in xs and the three weights: the rows' gradient is the
    grouped product with the weights transposed, a weight's the sum over
    its expert's tiles of rows^T . cotangent, accumulated in float32 and
    rounded once. The backward reads the two up products again, which the
    forward rule names (`GROUPED_RESIDUAL_NAMES`) for a checkpoint to keep;
    the activation's part is recomputed (elementwise)."""
    return _grouped_fwd(act, tile, xs, w_in, w_gate, w_out, tile_expert,
                        used)[0]


def _grouped_fwd(act, tile, xs, w_in, w_gate, w_out, tile_expert, used):
    h = _gmm(xs, w_in, tile_expert, used, tile)
    g = _gmm(xs, w_gate, tile_expert, used, tile)
    h = checkpoint_name(h, GROUPED_RESIDUAL_NAMES[0])
    g = checkpoint_name(g, GROUPED_RESIDUAL_NAMES[1])
    y = _gmm(act(g) * h, w_out, tile_expert, used, tile)
    return y, (xs, h, g, w_in, w_gate, w_out, tile_expert, used)


def _grouped_bwd(act, tile, res, dy):
    xs, h, g, w_in, w_gate, w_out, tile_expert, used = res
    E = w_in.shape[0]
    a, gated = jax.vjp(lambda g, h: act(g) * h, g, h)
    with jax.named_scope("experts_bwd_rows"):
        da = _gmm(dy, w_out, tile_expert, used, tile, True, "moe_gmm_dx")
        dg, dh = gated(da)
        dxs = (_gmm(dh, w_in, tile_expert, used, tile, True, "moe_gmm_dx")
               + _gmm(dg, w_gate, tile_expert, used, tile, True,
                      "moe_gmm_dx"))
    with jax.named_scope("experts_bwd_weights"):
        d_in = _tgmm(xs, dh, tile_expert, used, tile, E, w_in.dtype)
        d_gate = _tgmm(xs, dg, tile_expert, used, tile, E, w_gate.dtype)
        d_out = _tgmm(a, dy, tile_expert, used, tile, E, w_out.dtype)
    return dxs, d_in, d_gate, d_out, None, None


grouped_ffn.defvjp(_grouped_fwd, _grouped_bwd)
