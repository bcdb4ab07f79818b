"""A window in the flash kernels (ops/attention.py): forward and backward
against `mha_reference`, the kernels in interpret mode and their XLA
fallbacks, at windows under, at and over a block and at T <= window; and the
pin: with `window=None` the kernels are the parent's, op for op (their
jaxprs, their index maps, their compiler parameters)."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as A
from ray_tpu.ops.attention import flash_attention, mha_reference

B, H, KVH, D = 1, 4, 2, 128


def _qkv(T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, T, H, D)),
            jax.random.normal(ks[1], (B, T, KVH, D)),
            jax.random.normal(ks[2], (B, T, KVH, D)),
            jax.random.normal(ks[3], (B, T, H, D)))


# (T, window, block_q, block_k): under a block, a block, over one, unlike
# blocks both ways, T == window and T < window (no window: the causal
# kernels), a window of one key
SHAPES = [(512, 100, 128, 128), (512, 128, 128, 128), (512, 200, 128, 128),
          (512, 256, 128, 256), (512, 300, 256, 128), (256, 256, 128, 128),
          (256, 1000, 128, 128), (512, 1, 128, 128)]


@pytest.mark.parametrize("force", ["1", "0"], ids=["kernels", "xla"])
@pytest.mark.parametrize("T,window,bq,bk", SHAPES)
def test_window_agrees_with_the_reference(monkeypatch, force, T, window, bq, bk):
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", force)
    q, k, v, do = _qkv(T)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * do)

    ours = functools.partial(flash_attention, block_q=bq, block_k=bk,
                             window=window)
    ref = functools.partial(mha_reference, window=window)
    np.testing.assert_allclose(ours(q, k, v), ref(q, k, v), atol=5e-6)
    for got, want in zip(jax.grad(loss(ours), (0, 1, 2))(q, k, v),
                         jax.grad(loss(ref), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_window_off_by_one_is_told_apart():
    q, k, v, _ = _qkv(256)
    a = mha_reference(q, k, v, window=64)
    b = mha_reference(q, k, v, window=65)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    got = flash_attention(q, k, v, block_q=128, block_k=128, window=64)
    assert float(jnp.max(jnp.abs(got - a))) < 5e-6


def test_the_window_kernels_visit_the_blocks_a_window_reaches():
    """The grid's innermost axis is the window's span of blocks, not the
    sequence's: 3 key blocks a query block at 8 x 1024 under a window of
    2048 (8 without), and as many query blocks a key block."""
    assert A._key_span(2048, 1024, 1024, 8) == 3
    assert A._query_span(2048, 1024, 1024, 8, 8) == 3
    assert A._key_span(100, 128, 128, 4) == 2
    assert A._key_span(1, 128, 128, 4) == 1
    assert A._key_span(300, 256, 128, 2) == 4
    q = jax.ShapeDtypeStruct((1, 4, 1024, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, 1024, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(functools.partial(
        A._flash_fwd_pallas, causal=True, scale=1.0, block_q=128, block_k=128,
        window=200))(q, k, k))
    assert "name=flash_fwd_window" in text
    assert re.search(r"grid=\(1, 4, 8, 3\)", text)


def test_a_window_is_causal_self_attention():
    q, k, v, _ = _qkv(256)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k[:, :128], v[:, :128], window=8)


def kernels_text(fn, *args):
    """The jaxpr of `fn`, and of every Pallas call inside it the index maps
    of its blocks (a BlockMapping prints its shape alone)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    parts = [re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))]

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                for bm in eqn.params["grid_mapping"].block_mappings:
                    parts.append(str(bm.index_map_jaxpr))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return "\n".join(parts)


def no_window_texts(attention):
    q = jax.ShapeDtypeStruct((1, 4, 256, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 2, 256, 128), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((1, 4, 256), jnp.float32)
    kw = dict(causal=True, scale=0.125, block_q=128, block_k=128)
    return {
        "fwd": kernels_text(functools.partial(
            attention._flash_fwd_pallas, return_lse=True, **kw), q, k, k),
        "bwd": kernels_text(functools.partial(
            attention._flash_bwd_pallas, **kw), q, k, k, q, lse, q),
    }


# sha256 of `no_window_texts` on 72f82d8 (jax 0.9.0, interpret mode on the
# CPU, under tests/conftest.py's `highest` matmul precision): the causal
# forward kernel, which neither a window nor the one-pass backward touched
PARENT_FORWARD = "91dfd9ccbba31d45b767cb0969753024fdaa3365fc7f1011e51ca984838a5f6b"


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_without_a_window_the_kernels_are_the_parents(monkeypatch, which):
    """The forward op for op; the backward (since PR 55 ONE kernel where a
    dq and a dkv kernel stood) by what it is called, its grid and what
    leaves it: the name the benchmark counts a backward pass by, key blocks
    outside query blocks, and dk, dv and dq in float32."""
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")
    assert jax.__version__ == "0.9.0"
    text = no_window_texts(A)[which]
    if which == "fwd":
        assert hashlib.sha256(text.encode()).hexdigest() == PARENT_FORWARD
        return
    assert "vmem_limit_bytes" in text
    assert re.findall(r"name=(flash\w+)", text) == ["flash_bwd_dq"]
    assert re.findall(r"grid=(\([^)]*\))", text) == ["(1, 4, 2, 2)"]
    assert "dimension_semantics=('parallel', 'parallel', 'arbitrary', 'arbitrary')" in text
    assert text.count("pallas_call[") == 1
    assert "out_avals=(" + ", ".join(
        ["ShapedArray(float32[1,4,256,128])"] * 3) + ")" in text
