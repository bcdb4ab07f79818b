"""Kernel correctness: Pallas (interpret mode on CPU) and XLA fallbacks vs
O(T^2) references, plus gradient checks for the custom VJPs."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    apply_rope,
    flash_attention,
    layer_norm,
    mha_reference,
    paged_attention_decode,
    rms_norm,
    rms_norm_reference,
    rope_frequencies,
)
from ray_tpu.ops.attention import _fwd_xla_blockwise
from ray_tpu.ops.paged_attention import _paged_reference


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


@pytest.fixture(params=["xla", "pallas"])
def kernel_mode(request, monkeypatch):
    monkeypatch.setenv(
        "RAY_TPU_FORCE_PALLAS", "1" if request.param == "pallas" else "0"
    )
    return request.param


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("kvh", [4, 1])
    def test_matches_reference(self, kernel_mode, causal, kvh):
        B, T, H, D = 2, 256, 4, 128
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, T, kvh, D))
        v = _rand(ks[2], (B, T, kvh, D))
        out = flash_attention(q, k, v, causal=causal)
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_xla_blockwise_lse(self):
        B, H, T, D = 1, 2, 256, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = _rand(ks[0], (B, H, T, D))
        k = _rand(ks[1], (B, H, T, D))
        v = _rand(ks[2], (B, H, T, D))
        o, lse = _fwd_xla_blockwise(q, k, v, causal=True, scale=D**-0.5, block_k=128)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D**-0.5
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(mask, s, -2e30)
        ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(lse, ref_lse, atol=1e-4, rtol=1e-4)

    def test_grads_match_reference(self, kernel_mode):
        B, T, H, D = 1, 256, 2, 128
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, T, H, D))
        v = _rand(ks[2], (B, T, H, D))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)

    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_grads_match_reference(self, kernel_mode, causal):
        # kvh < H exercises the per-q-head dk/dv group-sum in the Pallas bwd
        B, T, H, KVH, D = 1, 256, 4, 2, 128
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, T, KVH, D))
        v = _rand(ks[2], (B, T, KVH, D))

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-3)

    @pytest.mark.parametrize("t", [200, 129])
    def test_non_multiple_seq_len(self, kernel_mode, t):
        # regression: XLA fallback must handle T in (128, 256) not divisible
        # by the kv block (kv is padded + masked internally)
        B, H, D = 1, 2, 128
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = _rand(ks[0], (B, t, H, D))
        k = _rand(ks[1], (B, t, H, D))
        v = _rand(ks[2], (B, t, H, D))
        out = flash_attention(q, k, v, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)
        g = jax.grad(lambda a, b, c: jnp.sum(flash_attention(a, b, c) ** 2), 1)(q, k, v)
        g_ref = jax.grad(lambda a, b, c: jnp.sum(mha_reference(a, b, c) ** 2), 1)(q, k, v)
        np.testing.assert_allclose(g, g_ref, atol=5e-3, rtol=5e-3)

    def test_uneven_blocks_fall_back(self, kernel_mode):
        # T not divisible by block, D not multiple of 128 -> XLA path.
        B, T, H, D = 1, 96, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, T, H, D))
        v = _rand(ks[2], (B, T, H, D))
        out = flash_attention(q, k, v, causal=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)


# The one-pass backward (ops/attention.py `_bwd_kernel`): dq's blocks are
# read, added to and written back by the kernel's own DMAs, so what matters
# is how soon a block comes again: (T, block_q, block_k, window, causal, H,
# KVH). nq of 1, 2, 3 and 8 (the same block in consecutive steps, every
# other step, ...), GQA groups of 1, 4 and 8, windows whose last span names
# blocks past the sequence's end, at like and unlike blocks.
_ONE_PASS = [
    (128, 128, 128, None, True, 2, 2), (128, 128, 128, None, False, 2, 2),
    (256, 128, 128, None, True, 4, 1), (256, 128, 128, None, False, 4, 1),
    (384, 128, 128, None, True, 8, 1), (384, 128, 128, None, False, 8, 1),
    (1024, 128, 128, None, True, 2, 2), (1024, 128, 128, None, False, 2, 1),
    (512, 256, 128, None, True, 4, 1), (512, 128, 256, None, True, 4, 1),
    (384, 128, 128, None, False, 4, 4),
    (640, 128, 128, 300, True, 4, 1), (640, 128, 128, 129, True, 8, 1),
    (768, 256, 128, 300, True, 2, 2), (768, 128, 256, 200, True, 4, 1),
    (1024, 128, 128, 256, True, 2, 1), (256, 128, 128, 100, True, 8, 1),
]


class TestFlashBackwardOnePass:
    @pytest.fixture(autouse=True)
    def _kernels(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")

    @staticmethod
    def _qkv(T, H, KVH, seed=11):
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        return (_rand(ks[0], (1, T, H, 128)), _rand(ks[1], (1, T, KVH, 128)),
                _rand(ks[2], (1, T, KVH, 128)), _rand(ks[3], (1, T, H, 128)))

    @pytest.mark.parametrize("T,bq,bk,window,causal,H,KVH", _ONE_PASS)
    def test_dq_dk_dv_match_reference(self, T, bq, bk, window, causal, H, KVH):
        q, k, v, do = self._qkv(T, H, KVH)

        def loss(attend):
            return lambda q, k, v: jnp.sum(attend(q, k, v) * do)

        ours = functools.partial(flash_attention, causal=causal, block_q=bq,
                                 block_k=bk, window=window)
        ref = functools.partial(mha_reference, causal=causal, window=window)
        for got, want in zip(jax.grad(loss(ours), (0, 1, 2))(q, k, v),
                             jax.grad(loss(ref), (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(got, want, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("T", [128, 384])
    def test_an_lse_cotangent_folds_in(self, T, causal):
        """Ring attention's merge differentiates through lse: the cotangent
        shifts delta, in the one kernel as in the pair before it."""
        from ray_tpu.ops.attention import flash_attention_with_lse

        q, k, v, do = self._qkv(T, 4, 2)
        dl = _rand(jax.random.PRNGKey(12), (1, 4, T))

        def ours(q, k, v):
            o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                              block_q=128, block_k=128)
            return jnp.sum(o * do) + jnp.sum(lse * dl)

        def ref(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2),
                           precision="highest") * 128 ** -0.5
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -2e30)
            lse = jax.scipy.special.logsumexp(s, axis=-1)
            return (jnp.sum(mha_reference(q, k, v, causal=causal) * do)
                    + jnp.sum(lse * dl))

        for got, want in zip(jax.grad(ours, (0, 1, 2))(q, k, v),
                             jax.grad(ref, (0, 1, 2))(q, k, v)):
            np.testing.assert_allclose(got, want, atol=2e-5)


class TestNorms:
    def test_rms_norm(self, kernel_mode):
        x = _rand(jax.random.PRNGKey(0), (4, 256, 256))
        w = _rand(jax.random.PRNGKey(1), (256,)) * 0.1 + 1.0
        np.testing.assert_allclose(
            rms_norm(x, w), rms_norm_reference(x, w), atol=1e-5, rtol=1e-5
        )

    def test_rms_norm_grad(self, kernel_mode):
        x = _rand(jax.random.PRNGKey(0), (8, 256))
        w = jnp.ones((256,))

        def f(x, w):
            return jnp.sum(rms_norm(x, w) ** 2)

        def f_ref(x, w):
            return jnp.sum(rms_norm_reference(x, w) ** 2)

        gx, gw = jax.grad(f, argnums=(0, 1))(x, w)
        gx_r, gw_r = jax.grad(f_ref, argnums=(0, 1))(x, w)
        np.testing.assert_allclose(gx, gx_r, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gw, gw_r, atol=1e-4, rtol=1e-4)

    def test_layer_norm(self):
        x = _rand(jax.random.PRNGKey(0), (4, 32))
        w, b = jnp.ones((32,)), jnp.zeros((32,))
        y = layer_norm(x, w, b)
        np.testing.assert_allclose(jnp.mean(y, -1), 0.0, atol=1e-5)
        np.testing.assert_allclose(jnp.std(y, -1), 1.0, atol=1e-2)


class TestRope:
    def test_norm_preserved(self):
        cos, sin = rope_frequencies(64, 128)
        x = _rand(jax.random.PRNGKey(0), (2, 100, 4, 64))
        y = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            jnp.linalg.norm(y, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5
        )

    def test_position_zero_identity(self):
        cos, sin = rope_frequencies(64, 128)
        x = _rand(jax.random.PRNGKey(0), (1, 1, 2, 64))
        y = apply_rope(x, cos, sin, positions=jnp.zeros((1, 1), jnp.int32))
        np.testing.assert_allclose(y, x, atol=1e-6)

    def test_relative_property(self):
        # <rope(q,m), rope(k,n)> depends only on m-n.
        cos, sin = rope_frequencies(64, 256)
        q = _rand(jax.random.PRNGKey(0), (1, 1, 1, 64))
        k = _rand(jax.random.PRNGKey(1), (1, 1, 1, 64))

        def score(m, n):
            qm = apply_rope(q, cos, sin, positions=jnp.full((1, 1), m, jnp.int32))
            kn = apply_rope(k, cos, sin, positions=jnp.full((1, 1), n, jnp.int32))
            return jnp.sum(qm * kn)

        np.testing.assert_allclose(score(5, 3), score(102, 100), atol=1e-4)


def _pool(key, L, KVH, P, ps, D):
    """A random page pool in the layout the ops own."""
    from ray_tpu.ops import pool_shape

    return _rand(key, pool_shape(L, P, ps, KVH, D))


def _tokens(pool, layer, pages, KVH):
    """numpy [n*ps, KVH, D]: the tokens of `pages`, in order. Written out
    here and not taken from the ops: head c is lanes c*D .. of a row."""
    rows = np.asarray(pool)[layer, 0][np.asarray(pages)]  # [n, ps, KVH*D]
    rows = rows.reshape(-1, rows.shape[-1])
    D = rows.shape[-1] // KVH
    return np.stack([rows[:, c * D:(c + 1) * D] for c in range(KVH)], axis=1)


def _plain(q, k, v, seen, scale):
    """Per-head softmax over the visible tokens, in numpy, sharing no code
    with the ops. q [R,H,D], k/v [T,KVH,D], seen [R,T] bool -> [R,H,D]."""
    R, H, D = q.shape
    g = H // k.shape[1]
    out = np.zeros((R, H, D), np.float64)
    for r in range(R):
        if not seen[r].any():
            continue
        for h in range(H):
            s = (k[seen[r], h // g].astype(np.float64)
                 @ q[r, h].astype(np.float64)) * scale
            p = np.exp(s - s.max())
            out[r, h] = (p / p.sum()) @ v[seen[r], h // g]
    return out


class TestPagedAttention:
    def _setup(self, B=3, H=4, KVH=2, D=128, page_size=16, pages_per_seq=8):
        total_pages = B * pages_per_seq + 1
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (B, H, D))
        # a pool of one layer: the ops take the pool whole and a layer
        k_pages = _pool(ks[1], 1, KVH, total_pages, page_size, D)
        v_pages = _pool(ks[2], 1, KVH, total_pages, page_size, D)
        # Page 0 reserved; each seq uses disjoint pages.
        page_table = (
            1 + jnp.arange(B * pages_per_seq, dtype=jnp.int32)
        ).reshape(B, pages_per_seq)
        lengths = jnp.array([37, 128, 1], dtype=jnp.int32)
        return q, k_pages, v_pages, page_table, lengths

    def test_matches_dense(self, kernel_mode):
        q, kp, vp, pt, lens = self._setup()
        out = paged_attention_decode(q, kp, vp, pt, lens, layer=0)
        ref = _paged_reference(q, kp, vp, pt, lens, 0, q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_against_flash(self, kernel_mode):
        # Build a contiguous cache, run dense attention on the prefix, and
        # compare with the paged view of the same data.
        B, H, KVH, D, ps, pps = 2, 4, 4, 128, 16, 4
        q, kp, vp, pt, _ = self._setup(B, H, KVH, D, ps, pps)
        lens = jnp.array([64, 33], dtype=jnp.int32)
        out = paged_attention_decode(q, kp, vp, pt, lens, layer=0)
        for b in range(B):
            L = int(lens[b])
            o_ref = mha_reference(
                q[b][None, None],  # [1, 1, H, D]
                jnp.asarray(_tokens(kp, 0, pt[b], KVH)[:L])[None],
                jnp.asarray(_tokens(vp, 0, pt[b], KVH)[:L])[None],
                causal=False,
            )
            np.testing.assert_allclose(out[b], o_ref[0, 0], atol=2e-3, rtol=2e-3)


# the cells' shapes (8 kv heads, groups of 4), multi-query, a pair, and a
# row that is no power of two wide
_HEADS = [(32, 8), (4, 1), (4, 2), (20, 10)]


class TestPoolRowAgainstPlainSoftmax:
    """Decode, chunk and verify on the pool (a token's kv heads in one row)
    against `_plain`, which shares no code with the ops."""

    B, D, PS, PPS, L = 3, 128, 16, 4, 2

    def _pool_and_table(self, KVH):
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        P = self.B * self.PPS + 1
        # a table in no particular order: a page's place is the table's say
        table = 1 + jax.random.permutation(ks[2], self.B * self.PPS).reshape(
            self.B, self.PPS).astype(jnp.int32)
        return (_pool(ks[0], self.L, KVH, P, self.PS, self.D),
                _pool(ks[1], self.L, KVH, P, self.PS, self.D), table)

    @pytest.mark.parametrize("window", [None, 24])
    @pytest.mark.parametrize("H,KVH", _HEADS)
    def test_decode(self, kernel_mode, H, KVH, window):
        kp, vp, pt = self._pool_and_table(KVH)
        q = _rand(jax.random.PRNGKey(12), (self.B, H, self.D))
        lens = np.array([5, 33, 64], np.int32)
        out = paged_attention_decode(q, kp, vp, pt, jnp.asarray(lens), layer=1,
                                     window=window)
        pos = np.arange(self.PPS * self.PS)
        for b in range(self.B):
            seen = pos < lens[b]
            if window is not None:
                seen &= pos >= lens[b] - window
            ref = _plain(np.asarray(q[b:b + 1]), _tokens(kp, 1, pt[b], KVH),
                         _tokens(vp, 1, pt[b], KVH), seen[None],
                         self.D ** -0.5)
            np.testing.assert_allclose(out[b], ref[0], atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("window", [None, 24])
    @pytest.mark.parametrize("H,KVH", _HEADS)
    def test_chunk(self, kernel_mode, H, KVH, window):
        from ray_tpu.ops import paged_attention_chunk

        kp, vp, pt = self._pool_and_table(KVH)
        C, start = 16, 20
        q = _rand(jax.random.PRNGKey(13), (C, H, self.D))
        out = paged_attention_chunk(q, kp, vp, pt[1], start, start + C,
                                    layer=1, window=window)
        pos = np.arange(self.PPS * self.PS)[None]
        qpos = start + np.arange(C)[:, None]
        seen = (pos <= qpos) & (pos < start + C)
        if window is not None:
            seen &= pos > qpos - window
        ref = _plain(np.asarray(q), _tokens(kp, 1, pt[1], KVH),
                     _tokens(vp, 1, pt[1], KVH), seen, self.D ** -0.5)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("H,KVH", _HEADS)
    def test_verify(self, kernel_mode, H, KVH):
        from ray_tpu.ops import paged_attention_verify

        kp, vp, pt = self._pool_and_table(KVH)
        S = 3
        q = _rand(jax.random.PRNGKey(14), (self.B, S, H, self.D))
        at = np.array([4, 30, 50], np.int32)
        out = paged_attention_verify(q, kp, vp, pt, jnp.asarray(at), layer=1)
        pos = np.arange(self.PPS * self.PS)[None]
        for b in range(self.B):
            seen = pos <= at[b] + np.arange(S)[:, None]
            ref = _plain(np.asarray(q[b]), _tokens(kp, 1, pt[b], KVH),
                         _tokens(vp, 1, pt[b], KVH), seen, self.D ** -0.5)
            np.testing.assert_allclose(out[b], ref, atol=2e-3, rtol=2e-3)

    # A step of the kernels' loop is a BLOCK of pages (`_block_pages`); a
    # slot of length 0 holds no sequence. `how`: the XLA forms; the
    # kernels with the block the rule gives (here a whole table) and with
    # blocks of 2 and 3 pages, each also with NaN in every page that is
    # not a live sequence's own (a block's tail fetches nothing, and what
    # it does not fetch reaches neither product).

    @pytest.fixture(params=[("xla", None, False)] + [
        ("pallas", n, nan) for n in (None, 2, 3) for nan in (False, True)],
        ids=lambda h: f"{h[0]}-block{h[1]}" + ("-nan" if h[2] else ""))
    def how(self, request, monkeypatch):
        mode, block, nan = request.param
        monkeypatch.setenv("RAY_TPU_FORCE_PALLAS",
                           "1" if mode == "pallas" else "0")
        if block is not None:
            from ray_tpu.ops import paged_attention as pa

            monkeypatch.setattr(
                pa, "_block_pages",
                lambda ps, width, dtype, rows, pages: min(block, pages))
        return block, nan

    BB, PP = 6, 6  # slots and pages a slot of the block cases

    def _edge(self, block):
        """A length that ends a block exactly, more than one block in."""
        return (2 if block == 2 else 1) * (block or 3) * self.PS

    def _case(self, KVH, D, own, nan):
        """Pools and a shuffled table of BB slots; with `nan`, every page
        but the first own[b] of slot b's holds NaN, in every layer."""
        ks = jax.random.split(jax.random.PRNGKey(21), 3)
        n = self.BB * self.PP
        table = 1 + jax.random.permutation(ks[2], n).reshape(
            self.BB, self.PP).astype(jnp.int32)
        kp = _pool(ks[0], self.L, KVH, n + 1, self.PS, D)
        vp = _pool(ks[1], self.L, KVH, n + 1, self.PS, D)
        if nan:
            keep = np.zeros(n + 1, bool)
            for b, pages in enumerate(own):
                keep[np.asarray(table[b, :pages])] = True
            hole = jnp.asarray(~keep)[None, None, :, None, None]
            kp, vp = (jnp.where(hole, jnp.nan, x) for x in (kp, vp))
        return kp, vp, table

    @pytest.mark.parametrize("window", [None, 24])
    @pytest.mark.parametrize("H,KVH,D", [(h, c, 128) for h, c in _HEADS]
                             + [(8, 4, 64)])
    def test_decode_blocks_and_slots_without_a_sequence(self, how, H, KVH, D,
                                                        window):
        block, nan = how
        e = self._edge(block)
        # dead slots before, between and after the live ones; lengths that
        # end a block exactly, one short and one past
        lens = np.array([0, e, 0, e - 1, e + 1, 0], np.int32)
        kp, vp, pt = self._case(KVH, D, -(-lens // self.PS), nan)
        q = _rand(jax.random.PRNGKey(22), (self.BB, H, D))
        out = np.asarray(paged_attention_decode(
            q, kp, vp, pt, jnp.asarray(lens), layer=1, window=window))
        pos = np.arange(self.PP * self.PS)
        for b in range(self.BB):
            if not lens[b]:
                np.testing.assert_array_equal(out[b], 0)
                continue
            seen = pos < lens[b]
            if window is not None:
                seen &= pos >= lens[b] - window
            own = np.asarray(pt[b, :-(-lens[b] // self.PS)])
            ref = _plain(np.asarray(q[b:b + 1]), _tokens(kp, 1, own, KVH),
                         _tokens(vp, 1, own, KVH), seen[None, :own.size * self.PS],
                         D ** -0.5)
            np.testing.assert_allclose(out[b], ref[0], atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("window", [None, 24])
    def test_decode_of_no_sequence_at_all_is_zeros(self, how, window):
        _, nan = how
        kp, vp, pt = self._case(2, self.D, [0] * self.BB, nan)
        q = _rand(jax.random.PRNGKey(23), (self.BB, 4, self.D))
        out = paged_attention_decode(
            q, kp, vp, pt, jnp.zeros((self.BB,), jnp.int32), layer=1,
            window=window)
        np.testing.assert_array_equal(np.asarray(out), 0)

    @pytest.mark.parametrize("window", [None, 24])
    @pytest.mark.parametrize("H,KVH,D", [(32, 8, 128), (4, 1, 128), (8, 4, 64)])
    def test_chunk_with_total_at_a_blocks_edge(self, how, H, KVH, D, window):
        from ray_tpu.ops import paged_attention_chunk

        block, nan = how
        C, e = 16, self._edge(block)
        q = _rand(jax.random.PRNGKey(24), (C, H, D))
        pos = np.arange(self.PP * self.PS)[None]
        for total in (e - 1, e, e + 1):
            start = total - C
            own = [0] * self.BB
            own[1] = -(-total // self.PS)
            kp, vp, pt = self._case(KVH, D, own, nan)
            out = paged_attention_chunk(q, kp, vp, pt[1], start, total,
                                        layer=1, window=window)
            qpos = start + np.arange(C)[:, None]
            seen = (pos <= qpos) & (pos < total)
            if window is not None:
                seen &= pos > qpos - window
            pages = np.asarray(pt[1, :own[1]])
            ref = _plain(np.asarray(q), _tokens(kp, 1, pages, KVH),
                         _tokens(vp, 1, pages, KVH),
                         seen[:, :pages.size * self.PS], D ** -0.5)
            np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("H,KVH,D", [(32, 8, 128), (4, 1, 128), (8, 4, 64)])
    def test_verify_with_total_at_a_blocks_edge(self, how, H, KVH, D):
        from ray_tpu.ops import paged_attention_verify

        block, nan = how
        S, e = 3, self._edge(block)
        # spans that end a block exactly, one short and one past
        at = np.array([5, e - S, 9, e - S - 1, e - S + 1, 0], np.int32)
        kp, vp, pt = self._case(KVH, D, -(-(at + S) // self.PS), nan)
        q = _rand(jax.random.PRNGKey(25), (self.BB, S, H, D))
        out = paged_attention_verify(q, kp, vp, pt, jnp.asarray(at), layer=1)
        for b in range(self.BB):
            pages = np.asarray(pt[b, :-(-(at[b] + S) // self.PS)])
            seen = (np.arange(pages.size * self.PS)[None]
                    <= at[b] + np.arange(S)[:, None])
            ref = _plain(np.asarray(q[b]), _tokens(kp, 1, pages, KVH),
                         _tokens(vp, 1, pages, KVH), seen, D ** -0.5)
            np.testing.assert_allclose(out[b], ref, atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("KVH", [1, 2, 8, 10])
    def test_scatter_then_gather_returns_its_input(self, KVH):
        from ray_tpu.ops import gather_pages, scatter_pages

        kp, vp, _ = self._pool_and_table(KVH)
        ks = jax.random.split(jax.random.PRNGKey(15), 2)
        L, n = self.L, 3
        # 3 whole pages and a tail that the scatter leaves out
        k = _rand(ks[0], (L, n * self.PS + 5, KVH, self.D))
        v = _rand(ks[1], (L, n * self.PS + 5, KVH, self.D))
        pages = jnp.array([7, 2, 9], jnp.int32)
        kp2, vp2 = scatter_pages(kp, vp, k, v, pages)
        gk, gv = gather_pages(kp2, vp2, pages, KVH)
        np.testing.assert_array_equal(gk, k[:, : n * self.PS])
        np.testing.assert_array_equal(gv, v[:, : n * self.PS])
        # a token's row is its heads side by side, and no other page moved
        np.testing.assert_array_equal(
            _tokens(kp2, 1, pages, KVH), np.asarray(k[1, : n * self.PS]))
        untouched = np.setdiff1d(np.arange(kp.shape[2]), np.asarray(pages))
        np.testing.assert_array_equal(np.asarray(kp2)[:, :, untouched],
                                      np.asarray(kp)[:, :, untouched])


class TestBlockRule:
    """`_block_pages` alone: how many pages a step of the page loop takes."""

    # the rows the tree has (lfm2, dense and Mixtral, phi, Olmo), a tp = 4
    # shard of the dense row, and the query rows that meet them
    @pytest.mark.parametrize("width,rows", [(512, 32), (1024, 32), (1280, 40),
                                            (3840, 30), (256, 8)])
    def test_a_decode_block_covers_what_is_in_flight_and_fits(self, width, rows):
        from ray_tpu.ops import paged_attention as pa

        ps = 16
        n = pa._block_pages(ps, width, jnp.bfloat16, rows, 2048)
        page = 2 * ps * width * 2  # k and v
        assert n * page >= pa._IN_FLIGHT_BYTES
        assert (n * ps) % 128 == 0  # lane-dense scores
        # both buffers of k and v, the float32 accumulator and the scores:
        # well under the 16 MiB of scoped VMEM a kernel gets by default
        assert 2 * n * page <= pa._SCRATCH_BYTES
        held = 2 * n * page + 4 * rows * (width + n * ps + 256)
        assert held <= 8 * 2 ** 20 + 2 ** 20

    def test_rows_and_tables_bound_it(self):
        from ray_tpu.ops import paged_attention as pa

        # the chunk kernel: 256 queries of a group of 4 against one head's
        # 128 lanes: the float32 scores hold the block, not the bytes
        n = pa._block_pages(16, 128, jnp.bfloat16, 1024, 2048)
        assert 4 * 1024 * n * 16 <= pa._SCORE_BYTES < 4 * 1024 * 2 * n * 16
        # never more than a table holds; one page is the loop of old
        assert pa._block_pages(16, 1024, jnp.bfloat16, 32, 5) == 5
        assert pa._block_pages(16, 1024, jnp.bfloat16, 32, 1) == 1
        # a row so wide that two blocks would not fit gets fewer pages
        wide = pa._block_pages(16, 64 * 1024, jnp.bfloat16, 32, 2048)
        assert 1 <= wide < 8
        assert 2 * wide * 2 * 16 * 64 * 1024 * 2 <= pa._SCRATCH_BYTES or wide == 1

    def test_it_reads_shapes_and_dtypes_alone(self):
        import inspect

        from ray_tpu.ops import paged_attention as pa

        assert list(inspect.signature(pa._block_pages).parameters) == [
            "page_size", "width", "dtype", "rows", "pages_per_seq"]
        assert set(pa._block_pages.__code__.co_names) <= {
            "jnp", "dtype", "itemsize", "max", "min", "_LANES",
            "_IN_FLIGHT_BYTES", "_SCORE_BYTES", "_SCRATCH_BYTES"}


class TestPagedAttentionTP:
    @pytest.mark.parametrize("kernel", ["decode", "decode_window", "verify"])
    def test_kernel_under_tp_shard_map(self, kernel_mode, kernel):
        # D=128 so the Pallas branch is taken (interpret on CPU): the kernel
        # must partition over tp via shard_map, each shard on its kv heads'
        # lanes of every row, and equal the one-device call bit for bit
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.comm.mesh import MeshSpec, build_mesh
        from ray_tpu.ops import paged_attention_verify

        B, H, KVH, D = 2, 8, 4, 128
        PGS, ps = 8, 8
        kp = _pool(jax.random.PRNGKey(1), 1, KVH, PGS, ps, D)
        vp = _pool(jax.random.PRNGKey(2), 1, KVH, PGS, ps, D)
        table = jnp.array([[1, 2, 0, 0], [3, 4, 0, 0]], jnp.int32)
        lengths = jnp.array([13, 9], jnp.int32)
        if kernel == "verify":
            q = _rand(jax.random.PRNGKey(0), (B, 3, H, D))
            op = paged_attention_verify
        else:
            q = _rand(jax.random.PRNGKey(0), (B, H, D))
            op = functools.partial(
                paged_attention_decode,
                window=6 if kernel == "decode_window" else None)
        one = jax.jit(lambda *a: op(*a, layer=0))(q, kp, vp, table, lengths)
        seen = np.arange(4 * ps)[None] < np.asarray(lengths)[:, None]
        if kernel == "decode":  # and the one-device call is right
            for b in range(B):
                ref = _plain(np.asarray(q[b:b + 1]), _tokens(kp, 0, table[b], KVH),
                             _tokens(vp, 0, table[b], KVH), seen[b:b + 1],
                             D ** -0.5)
                np.testing.assert_allclose(one[b], ref[0], atol=2e-3, rtol=2e-3)

        mesh = build_mesh(MeshSpec.create(tp=2), devices=jax.devices("cpu")[:2])
        heads = P(*[None] * (q.ndim - 2), "tp", None)
        qs = jax.device_put(q, NamedSharding(mesh, heads))
        # the pool shards on its last axis: a shard's row is its kv heads'
        rows = NamedSharding(mesh, P(None, None, None, None, "tp"))
        kps, vps = jax.device_put(kp, rows), jax.device_put(vp, rows)
        ts = jax.device_put(table, NamedSharding(mesh, P()))
        ls = jax.device_put(lengths, NamedSharding(mesh, P()))
        out = jax.jit(
            lambda *a: op(*a, layer=0, mesh=mesh)
        )(qs, kps, vps, ts, ls)
        if kernel_mode == "pallas":
            np.testing.assert_array_equal(out, one)
        else:  # GSPMD partitions the reference's einsums as it likes
            np.testing.assert_allclose(out, one, atol=1e-5, rtol=1e-5)


class TestPagedAttentionChunk:
    """Chunked-prefill attention kernel (ops.paged_attention_chunk): a
    C-token query block over ONE sequence's paged KV with the per-row
    causal bound (key j visible to row c iff j <= start+c and j < total).
    Pallas branch runs in interpret mode on CPU via kernel_mode."""

    def _setup(self, C=32, H=6, KVH=2, D=128, page_size=16, pages_per_seq=8):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = _rand(ks[0], (C, H, D))
        kp = _pool(ks[1], 1, KVH, pages_per_seq + 4, page_size, D)
        vp = _pool(ks[2], 1, KVH, pages_per_seq + 4, page_size, D)
        pt = (1 + jnp.arange(pages_per_seq, dtype=jnp.int32))
        return q, kp, vp, pt

    @pytest.mark.parametrize("start,extra", [(0, 0), (37, 0), (0, -19)])
    def test_matches_reference(self, kernel_mode, start, extra):
        from ray_tpu.ops.paged_attention import (
            _chunk_reference,
            paged_attention_chunk,
        )

        q, kp, vp, pt = self._setup()
        C = q.shape[0]
        total = start + C + extra  # extra<0: visibility cap mid-chunk
        out = paged_attention_chunk(q, kp, vp, pt, start, total, layer=0)
        ref = _chunk_reference(q, kp, vp, pt, start, total, 0,
                               q.shape[-1] ** -0.5)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_matches_causal_flash_at_start_zero(self, kernel_mode):
        # start=0, total=C: the chunk IS the whole sequence — must equal
        # plain causal attention over the same contiguous KV
        from ray_tpu.ops.paged_attention import paged_attention_chunk

        C, H, KVH, D, ps = 32, 4, 4, 128, 16
        q, kp, vp, pt = self._setup(C, H, KVH, D, ps, pages_per_seq=2)
        out = paged_attention_chunk(q, kp, vp, pt, 0, C, layer=0)
        o_ref = mha_reference(
            q[None],  # [1, C, H, D]
            jnp.asarray(_tokens(kp, 0, pt, KVH)[:C])[None],
            jnp.asarray(_tokens(vp, 0, pt, KVH)[:C])[None],
            causal=True,
        )
        np.testing.assert_allclose(out, o_ref[0], atol=2e-3, rtol=2e-3)


class TestPagedLayerOfTheWholePool:
    """The ops take the pool whole, [L, 1, P, ps, KVH*D], and a layer: the
    result is the XLA reference's on that layer's slab alone, and no
    other layer is read (they hold NaN)."""

    L, B, S, C, H, KVH, D, PS, PPS = 3, 2, 3, 32, 4, 2, 128, 16, 4

    def _pool(self):
        ks = jax.random.split(jax.random.PRNGKey(7), 2)
        dims = (self.L, self.KVH, self.B * self.PPS + 1, self.PS, self.D)
        table = (1 + jnp.arange(self.B * self.PPS, dtype=jnp.int32)
                 ).reshape(self.B, self.PPS)
        return _pool(ks[0], *dims), _pool(ks[1], *dims), table

    def _cases(self):
        from ray_tpu.ops.paged_attention import (
            _chunk_reference,
            _verify_reference,
            paged_attention_chunk,
            paged_attention_verify,
        )

        kq = jax.random.PRNGKey(8)
        lens = jnp.array([37, 64], jnp.int32)
        pos = jnp.array([10, 37], jnp.int32)
        scale = self.D ** -0.5
        return {
            "decode": (
                _rand(kq, (self.B, self.H, self.D)),
                lambda q, kp, vp, pt, l: paged_attention_decode(
                    q, kp, vp, pt, lens, layer=l),
                lambda q, kp, vp, pt: _paged_reference(
                    q, kp, vp, pt, lens, 0, scale)),
            "chunk": (
                _rand(kq, (self.C, self.H, self.D)),
                lambda q, kp, vp, pt, l: paged_attention_chunk(
                    q, kp, vp, pt[0], 16, 16 + self.C, layer=l),
                lambda q, kp, vp, pt: _chunk_reference(
                    q, kp, vp, pt[0], 16, 16 + self.C, 0, scale)),
            "verify": (
                _rand(kq, (self.B, self.S, self.H, self.D)),
                lambda q, kp, vp, pt, l: paged_attention_verify(
                    q, kp, vp, pt, pos, layer=l),
                lambda q, kp, vp, pt: _verify_reference(
                    q, kp, vp, pt, pos, 0, scale)),
        }

    @pytest.mark.parametrize("layer", [0, L - 1])
    @pytest.mark.parametrize("kernel", ["decode", "chunk", "verify"])
    def test_layer_of_pool_equals_reference_on_its_slab(
            self, kernel_mode, kernel, layer):
        kp, vp, pt = self._pool()
        q, op, reference = self._cases()[kernel]
        ref = reference(q, kp[layer][None], vp[layer][None], pt)
        others = jnp.arange(self.L)[:, None, None, None, None] != layer
        # the layer is traced, as it is inside the engine's layer scan
        out = jax.jit(lambda q, kp, vp, l: op(q, kp, vp, pt, l))(
            q, jnp.where(others, jnp.nan, kp), jnp.where(others, jnp.nan, vp),
            jnp.int32(layer))
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_the_layers_differ(self):
        # what the parity above rests on: a wrong layer gives another answer
        kp, vp, pt = self._pool()
        q, op, _ = self._cases()["decode"]
        assert not np.allclose(op(q, kp, vp, pt, 0), op(q, kp, vp, pt, 2),
                               atol=1e-2)

    @pytest.mark.parametrize("idx_shape", [(2,), (2, 3)])
    def test_write_then_attend_writes_one_row_per_token(self, idx_shape):
        from ray_tpu.ops import write_then_attend

        kp, vp, _ = self._pool()
        ks = jax.random.split(jax.random.PRNGKey(9), 2)
        k = _rand(ks[0], (*idx_shape, self.KVH, self.D))
        v = _rand(ks[1], (*idx_shape, self.KVH, self.D))
        n = int(np.prod(idx_shape))
        page = (1 + jnp.arange(n, dtype=jnp.int32)).reshape(idx_shape)
        slot = (jnp.arange(n, dtype=jnp.int32) * 5 % self.PS).reshape(idx_shape)
        seen = {}

        def attend(q, kp_, vp_, layer):
            seen["pool"] = (kp_, vp_, layer)
            return q

        o, kp2, vp2 = write_then_attend(attend, 1.0, k, v, kp, vp, 1, page,
                                        slot)
        assert o == 1.0 and seen["pool"] == (kp2, vp2, 1)
        want_k, want_v = np.array(kp), np.array(vp)
        for i in np.ndindex(*idx_shape):  # a token's heads side by side
            want_k[1, 0, int(page[i]), int(slot[i])] = np.concatenate(
                list(np.asarray(k[i])))
            want_v[1, 0, int(page[i]), int(slot[i])] = np.concatenate(
                list(np.asarray(v[i])))
        np.testing.assert_array_equal(kp2, want_k)
        np.testing.assert_array_equal(vp2, want_v)
