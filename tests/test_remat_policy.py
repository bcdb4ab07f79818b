"""What `cfg.remat` keeps (models/transformer.py `_remat`): the attention
half of a layer by name, so the backward recomputes the norms and the FFN
and runs no attention kernel or projection twice. CPU, float32 tiny presets:
sizes and jaxprs, never times."""

import contextlib
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax._src.core import jaxprs_in_params

from ray_tpu.comm.mesh import MeshSpec, build_mesh
from ray_tpu.models import get_config, init_params, loss_fn
from ray_tpu.models import transformer
from ray_tpu.models.transformer import forward_pp
from ray_tpu.ops import attention, flash_attention

B, T = 2, 32


def _batch(cfg, rows=B):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, T), 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


# case -> (preset, the forward that holds the layer loop)
CASES = {
    "dense": ("tiny-llama", None),
    "moe": ("tiny-moe", None),
    "forward_pp_body": ("tiny-llama", "pp"),
    "forward_pp_body_moe": ("tiny-moe", "pp"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_under_remat_are_those_without(case, cpu_mesh_devices):
    preset, loop = CASES[case]
    cfg = dataclasses.replace(get_config(preset), n_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, rows=4)
    forward_fn, mesh = None, None
    if loop == "pp":
        mesh = build_mesh(MeshSpec.create(dp=2, pp=2),
                          devices=cpu_mesh_devices[:4])
        forward_fn = functools.partial(forward_pp, mesh=mesh,
                                       num_microbatches=2)
    got = {}
    for remat in (True, False):
        c = dataclasses.replace(cfg, remat=remat)
        with mesh if mesh is not None else contextlib.nullcontext():
            got[remat] = jax.jit(jax.grad(lambda p: loss_fn(
                p, batch, c, forward_fn=forward_fn)[0]))(params)
    flat = jax.tree.leaves(got[False])
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in flat)
    for kept, plain in zip(jax.tree.leaves(got[True]), flat):
        np.testing.assert_allclose(kept, plain, rtol=1e-5, atol=1e-6)


def _one_layer(cfg):
    """-> (the layer loop's body, bare; its carry; one layer's leaves)"""
    params = init_params(cfg, jax.random.PRNGKey(0))
    x, rope = transformer._prologue(params, jnp.zeros((B, T), jnp.int32), cfg,
                                    None)
    return (lambda c, lp: transformer._block(c, lp, cfg, rope, None), x,
            jax.tree.map(lambda a: a[0], params["layers"]))


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-moe"])
def test_a_checkpointed_layer_keeps_the_attention_half_and_no_ffn_product(
        preset):
    cfg = dataclasses.replace(get_config(preset), remat=True)
    H, KVH, hd, D = cfg.n_heads, cfg.kv_heads, cfg.hdim, cfg.d_model
    assert cfg.d_ff not in (B, T, H, KVH, hd, D)  # a width of its own
    body, x, lp = _one_layer(cfg)
    kept = [(aval.shape, why) for aval, why in saved_residuals(
                transformer._remat(body, cfg), x, lp)
            if not why.startswith(("from the argument", "from a constant"))]
    assert sorted(shape for shape, _ in kept) == sorted([
        (B, T, H, hd), (B, T, KVH, hd), (B, T, KVH, hd),  # q, k, v, turned
        (B, H, T, hd), (B, H, T),   # the kernel's output and log-sum-exp
        (B, T, D),                  # x + attention: what ln2 reads
    ]), kept
    assert not [shape for shape, _ in kept if cfg.d_ff in shape]
    # without the policy the same layer would keep the FFN's products too
    assert [aval for aval, _ in saved_residuals(body, x, lp)
            if len(aval.shape) >= 3 and aval.shape[-1] == cfg.d_ff]


@pytest.fixture
def flash_forwards_in_grad(monkeypatch):
    """-> cfg -> how often the gradient's jaxpr holds the flash forward (a
    scan holds its body once whatever the depth, so: once a loop that runs
    it). The forward is wrapped in a jit of a name to count."""
    inner = attention._fwd_lse_dispatch

    def dispatch(q, k, v, *static):
        def named_flash_forward(q, k, v):
            return inner(q, k, v, *static)
        return jax.jit(named_flash_forward)(q, k, v)

    monkeypatch.setattr(attention, "_fwd_lse_dispatch", dispatch)

    def walk(jaxpr):
        return sum(
            (eqn.params.get("name") == "named_flash_forward")
            + sum(walk(sub) for sub in jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    def count(cfg):
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = _batch(cfg)
        return walk(jax.make_jaxpr(jax.grad(
            lambda p: loss_fn(p, batch, cfg)[0]))(params).jaxpr)

    return count


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-moe"])
def test_the_backward_holds_no_second_flash_forward(
        preset, flash_forwards_in_grad, monkeypatch):
    cfg = dataclasses.replace(get_config(preset), remat=True)
    # as many as with everything kept: the backward recomputes none
    kept_all = flash_forwards_in_grad(dataclasses.replace(cfg, remat=False))
    assert kept_all >= 1
    assert flash_forwards_in_grad(cfg) == kept_all
    # what the names buy: a checkpoint that keeps nothing runs it again
    monkeypatch.setattr(transformer, "_KEPT_UNDER_REMAT", ())
    assert flash_forwards_in_grad(cfg) == kept_all + 1
    # and so does one that keeps all but what the rule itself names: the
    # rule's residuals are what the backward kernels read
    monkeypatch.setattr(transformer, "_KEPT_UNDER_REMAT",
                        ("attn_q", "attn_k", "attn_v", "attn_half"))
    assert flash_forwards_in_grad(cfg) == kept_all + 1


def test_ring_attentions_rule_names_nothing():
    """Ring attention calls the lse-returning op once a ring step; naming
    its partials would keep every step's (ops/attention.py)."""
    q = jnp.ones((1, T, 4, 16))
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        attention.flash_attention_with_lse(q, q, q)[0])))(q)
    assert "name[" not in str(jaxpr)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        flash_attention(q, q, q))))(q)
    assert "name[name=flash_out]" in str(jaxpr)
    assert "name[name=flash_lse]" in str(jaxpr)


@pytest.mark.parametrize("differentiated", [False, True])
def test_a_name_outside_a_checkpoint_lowers_to_nothing(differentiated,
                                                       monkeypatch):
    """The serve path's bucket prefill calls the same op undifferentiated,
    and a gradient taken outside any checkpoint meets the names: both lower
    to the text they would have without them."""
    q = jnp.ones((1, T, 4, 16))
    k = jnp.ones((1, T, 2, 16))

    def lowered():
        def op(q, k, v):  # a fresh function: nothing cached across the patch
            if differentiated:
                return jax.grad(lambda q: jnp.sum(flash_attention(q, k, v)))(q)
            return flash_attention(q, k, v)
        return jax.jit(op).lower(q, k, k).as_text()

    named = lowered()
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    # (the lowering numbers its private functions from a counter of the process)
    def unnumbered(text):
        return re.sub(r"(@[A-Za-z_]+?)_\d+\b", r"\1", text)

    assert unnumbered(lowered()) == unnumbered(named)
