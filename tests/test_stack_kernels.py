"""The kernels a stack of unlike layers runs on the chip, in Pallas
interpret mode against their XLA forms: the paged decode and chunk kernels
with a window bound (a ring of pages in decode), at the width of a
differential pair (two 64-wide heads side by side in one 128-wide row), and
the selective scan with its one-token decode form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import ssm

TOL = 5e-6  # float32, same arithmetic in another order


@pytest.fixture(autouse=True)
def pallas_everywhere(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


def keys(n):
    return jax.random.split(jax.random.PRNGKey(28), n)


H, KVH, D, PS, W = 8, 2, 128, 4, 8
RING = W // PS + 1


def pool(key, layers, pages):
    """A random page pool, a token's KVH heads side by side in one row."""
    return jax.random.normal(key, pa.pool_shape(layers, pages, PS, KVH, D))


def heads(row):
    """[.., KVH * D] -> [.., KVH, D], written out: head c is lanes c*D .. ."""
    return jnp.stack([row[..., c * D:(c + 1) * D] for c in range(KVH)], -2)


@pytest.fixture(params=[None, 2], ids=["rule", "blocks-of-2"])
def block(request, monkeypatch):
    """The pages a step of the kernels' loop takes: what the rule gives (at
    these sizes a whole table) or 2, so that a ring of 3 wraps INSIDE a
    block and a window's first page falls in the middle of one."""
    if request.param:
        monkeypatch.setattr(pa, "_block_pages",
                            lambda ps, w, dt, rows, pages: min(2, pages))
    return request.param


@pytest.mark.parametrize("lengths", [(1, 3, 8), (9, 12, 13), (30, 41, 57),
                                     (0, 23, 0)])
def test_paged_decode_window_over_a_ring(lengths, block):
    """Below the window, at its edge, and after the ring has lapped; a slot
    of length 0 holds no sequence and reads zeros. The trash page and
    every other layer hold NaN: nothing of them is read."""
    k = keys(3)
    B = len(lengths)
    kp = pool(k[0], 2, 1 + B * RING).at[0].set(jnp.nan).at[:, :, 0].set(jnp.nan)
    vp = pool(k[1], 2, 1 + B * RING).at[0].set(jnp.nan).at[:, :, 0].set(jnp.nan)
    q = jax.random.normal(k[2], (B, H, D))
    table = (1 + jnp.arange(B)[:, None] * RING
             + jnp.arange(RING)[None]).astype(jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    got = jax.jit(lambda *a: pa.paged_attention_decode(
        *a, layer=1, window=W, scale=0.125))(q, kp, vp, table, lens)
    want = pa._paged_reference(q, kp, vp, table, lens, 1, 0.125, window=W)
    np.testing.assert_allclose(got, want, atol=TOL)
    # and against the keys laid out by position, no ring, no pages
    for b, n in enumerate(lengths):
        if not n:
            np.testing.assert_array_equal(got[b], 0)
            continue
        pos = range(max(0, n - W), n)
        kk = jnp.stack([heads(kp[1, 0, table[b, (p // PS) % RING], p % PS])
                        for p in pos], 1)
        vv = jnp.stack([heads(vp[1, 0, table[b, (p // PS) % RING], p % PS])
                        for p in pos], 1)
        s = jnp.einsum("cgd,ctd->cgt", q[b].reshape(KVH, H // KVH, D), kk) / 8
        o = jnp.einsum("cgt,ctd->cgd", jax.nn.softmax(s, -1), vv)
        np.testing.assert_allclose(got[b], o.reshape(H, D), atol=TOL)


@pytest.mark.parametrize("first", [0, 3, 8])
def test_paged_chunk_window_over_the_kept_tail(first, block):
    """The chunk program's window layers: tail and chunk side by side as a
    little pool, rows `first`.. the sequence's own."""
    k = keys(3)
    C = 8
    n = (W + C) // PS
    kb, vb = pool(k[0], 1, n), pool(k[1], 1, n)
    q = jax.random.normal(k[2], (C, H, D))
    table = jnp.arange(n, dtype=jnp.int32)
    got = jax.jit(lambda q, kb, vb, f: pa.paged_attention_chunk(
        q, kb, vb, table, W, W + C, 0, scale=0.125, window=W, first=f))(
            q, kb, vb, jnp.int32(first))
    want = pa._chunk_reference(q, kb, vb, table, W, W + C, 0, 0.125, W, first)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_a_differential_pair_rides_one_row():
    """[q1 ; 0] and [0 ; q2] against rows [k1 ; k2], [v1 ; v2] give the two
    softmaxes of differential attention over 64-wide heads, in one pass."""
    k = keys(5)
    B, hd, n = 2, 64, 11
    q1, q2 = (jax.random.normal(k[i], (B, hd)) for i in (0, 1))
    kk = jax.random.normal(k[2], (B, n, 2 * hd))
    vv = jax.random.normal(k[3], (B, n, 2 * hd))
    pages = -(-n // PS)
    pad = jnp.zeros((B, pages * PS - n, 2 * hd))

    def pool(x):  # [B,n,128] -> [1,1,1+B*pages,PS,128]
        x = jnp.concatenate([x, pad], 1).reshape(B * pages, PS, 2 * hd)
        return jnp.concatenate([jnp.zeros((1, PS, 2 * hd)), x])[None, None]

    zero = jnp.zeros_like(q1)
    q = jnp.stack([jnp.concatenate([q1, zero], -1),
                   jnp.concatenate([zero, q2], -1)], 1)  # [B,2,128]
    table = (1 + jnp.arange(B)[:, None] * pages
             + jnp.arange(pages)[None]).astype(jnp.int32)
    got = jax.jit(lambda *a: pa.paged_attention_decode(
        *a, layer=0, scale=hd ** -0.5))(q, pool(kk), pool(vv), table,
                                        jnp.full((B,), n, jnp.int32))
    for h, (qh, half) in enumerate(((q1, slice(0, hd)), (q2, slice(hd, None)))):
        s = jnp.einsum("bd,btd->bt", qh, kk[..., half]) / hd ** 0.5
        want = jnp.einsum("bt,btd->bd", jax.nn.softmax(s, -1), vv)
        np.testing.assert_allclose(got[:, h], want, atol=TOL)


def _scan_inputs(B, T, Di, N):
    k = keys(7)
    return (jax.random.normal(k[0], (B, T, Di)),
            jax.nn.softplus(jax.random.normal(k[1], (B, T, Di))),
            -jnp.exp(jax.random.normal(k[2], (N, Di))),
            jax.random.normal(k[3], (B, T, N)),
            jax.random.normal(k[4], (B, T, N)),
            jax.random.normal(k[5], (Di,)),
            jax.random.normal(k[6], (B, N, Di)))


@pytest.mark.parametrize("T,Di", [(8, 128), (32, 256), (256, 512)])
def test_ssm_scan_kernel_against_the_plain_scan(T, Di):
    """One time chunk, several, and several inner-width blocks: the state
    stays resident between chunks and comes out with the last."""
    args = _scan_inputs(2, T, Di, 4)
    y, s1 = jax.jit(ssm.ssm_scan)(*args)
    y_ref, s_ref = ssm.ssm_scan_reference(*args)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(s1, s_ref, atol=2e-5)


def test_ssm_scan_passes_over_padding():
    """dt = 0 leaves the state as it was: how padded positions are skipped."""
    u, dt, A, Bm, Cm, D, s0 = _scan_inputs(1, 16, 128, 4)
    dt = dt.at[:, 11:].set(0.0)
    _, s1 = jax.jit(ssm.ssm_scan)(u, dt, A, Bm, Cm, D, s0)
    _, want = ssm.ssm_scan_reference(u[:, :11], dt[:, :11], A, Bm[:, :11],
                                     Cm[:, :11], D, s0)
    np.testing.assert_allclose(s1, want, atol=TOL)


@pytest.mark.parametrize("layer", [0, 2])
def test_ssm_step_updates_one_layer_in_place(layer):
    u, dt, A, Bm, Cm, D, _ = _scan_inputs(8, 1, 256, 4)
    state = jax.random.normal(keys(9)[8], (3, 8, 4, 256))
    args = (state, jnp.int32(layer), u[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    y, new = jax.jit(ssm.ssm_step)(*args)
    y_ref, new_ref = ssm.ssm_step_reference(*args)
    np.testing.assert_allclose(y, y_ref, atol=TOL)
    np.testing.assert_allclose(new, new_ref, atol=TOL)
    others = np.asarray([l for l in range(3) if l != layer])
    np.testing.assert_array_equal(np.asarray(new)[others],
                                  np.asarray(state)[others])


def test_paged_chunk_reads_its_heads_tile_of_every_row():
    """kv head c is lanes c * D .. of every row: against the keys laid out
    by position and split by hand, no pages."""
    k = keys(3)
    C, P = 8, 7
    kp, vp = pool(k[0], 2, P), pool(k[1], 2, P)
    q = jax.random.normal(k[2], (C, H, D))
    table = jnp.asarray([3, 1, 5, 2, 0, 0], jnp.int32)
    got = jax.jit(lambda q, kp, vp: pa.paged_attention_chunk(
        q, kp, vp, table, 8, 16, 1))(q, kp, vp)
    kk = heads(kp[1, 0, table[:4]].reshape(16, KVH * D))  # [16, KVH, D]
    vv = heads(vp[1, 0, table[:4]].reshape(16, KVH * D))
    s = jnp.einsum("qcgd,tcd->qcgt", q.reshape(C, KVH, H // KVH, D), kk) * D ** -0.5
    seen = jnp.arange(16)[None] <= 8 + jnp.arange(C)[:, None]
    s = jnp.where(seen[:, None, None], s, -jnp.inf)
    want = jnp.einsum("qcgt,tcd->qcgd", jax.nn.softmax(s, -1), vv)
    np.testing.assert_allclose(got, want.reshape(C, H, D), atol=TOL)


def _grids(fn, *args):
    """The grid of every pallas_call in fn's jaxpr, nested calls included."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(tuple(eqn.params["grid_mapping"].grid))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


@pytest.mark.parametrize("window", [None, W])
def test_decode_is_one_grid_program_a_sequence(window):
    """A differential stack's heads (pairs of 128) and any other model's:
    the decode call's grid is the batch, whatever the number of kv heads,
    and each query head still meets its own kv head alone."""
    from ray_tpu.models import get_config

    cfg = get_config("tiny-sambay", n_heads=8, n_kv_heads=4, head_dim=64)
    k = keys(3)
    B, P = 3, 9
    assert (cfg.pool_heads, cfg.pool_dim) == (KVH, D)  # 2 pairs of 128
    kp, vp = pool(k[0], 1, P), pool(k[1], 1, P)
    q = jax.random.normal(k[2], (B, cfg.n_heads, D))
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 0, 0]], jnp.int32)
    lens = jnp.asarray([13, 5, 8], jnp.int32)

    def decode(q, kp, vp):
        return pa.paged_attention_decode(q, kp, vp, table, lens, 0,
                                         scale=0.125, window=window)

    # (one call per lowering platform of the dispatch)
    assert set(_grids(decode, q, kp, vp)) == {(B,)}
    got = jax.jit(decode)(q, kp, vp)
    for b in range(B):
        n = int(lens[b])
        pos = range(max(0, n - (window or n)), n)
        kk = jnp.stack([heads(kp[0, 0, table[b, p // PS], p % PS]) for p in pos], 1)
        vv = jnp.stack([heads(vp[0, 0, table[b, p // PS], p % PS]) for p in pos], 1)
        s = jnp.einsum("cgd,ctd->cgt", q[b].reshape(KVH, H // KVH, D), kk) / 8
        o = jnp.einsum("cgt,ctd->cgd", jax.nn.softmax(s, -1), vv)
        np.testing.assert_allclose(got[b], o.reshape(H, D), atol=TOL)


def test_decode_hands_a_slot_without_a_sequence_the_length_zero(monkeypatch):
    """Both paged calls of `Decode` (the window rings and the full cache):
    a slot whose table is the trash page attends over no key, so its grid
    program does nothing (`serve_decode_slot_steps{state="empty"}` counts
    them); a live slot over its position and the token it writes."""
    from ray_tpu.models import get_config, stack

    cfg = get_config("tiny-sambay")
    handed = {}

    def spy(q, kp, vp, table, lengths, layer, window=None, **_):
        handed[window] = np.asarray(lengths)
        return jnp.zeros_like(q)

    monkeypatch.setattr(stack, "paged_attention_decode", spy)
    ps = 4
    tables = jnp.asarray([[0, 0, 0], [3, 4, 0], [0, 0, 0], [5, 0, 0]], jnp.int32)
    mode = stack.Decode(cfg, jnp.asarray([0, 6, 0, 2], jnp.int32), tables, ps)
    B, Hq, hd = 4, cfg.n_heads, cfg.pool_dim
    q = jnp.zeros((B, 1, Hq, hd))
    kv = jnp.zeros((B, 1, cfg.pool_heads, hd))
    rows = cfg.pool_heads * hd
    full = jnp.zeros(pa.pool_shape(1, 6, ps, cfg.pool_heads, hd))
    rings = jnp.zeros(pa.pool_shape(1, 1 + B * mode.ring, ps, cfg.pool_heads, hd))
    carry = {"k_pages": full, "v_pages": full, "wk": rings, "wv": rings}
    mode.attend_full(carry, 0, q, None, None, 1.0)
    mode.attend_window(carry, 0, q, kv, kv, 1.0)
    assert rows == full.shape[-1]
    np.testing.assert_array_equal(handed[None], [0, 7, 0, 3])
    np.testing.assert_array_equal(handed[cfg.window], [0, 7, 0, 3])
