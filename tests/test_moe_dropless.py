"""The expert layer's dropless form (models/transformer.py
`_moe_ffn_dropless`): where no slot can overflow it is the padded forms'
sum with nothing dispatched, `_moe_ffn` picks it by a rule on the static
shape, and `moe_rows_computed` says what the program it lowers computes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_config
from ray_tpu.models import transformer as tr

D, F = 32, 48
# name -> (B, T): a decode step, a prefill chunk, a bucket of prompts
SHAPES = {"decode": (6, 1), "chunk": (1, 16), "bucket": (3, 8)}


def _cfg(gating, capacity_factor):
    """8 experts, top 2, float32: softmax over the chosen two on a plain
    `ModelConfig`, sigmoid scores + bias on a `StackConfig`."""
    base = get_config("tiny-lfm2" if gating == "sigmoid" else "tiny-moe")
    return dataclasses.replace(
        base, num_experts=8, num_selected_experts=2,
        capacity_factor=capacity_factor)


def _layer(cfg, B, T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    E = cfg.num_experts
    lp = {"router": jax.random.normal(ks[0], (D, E), jnp.float32),
          "router_bias": 0.3 * jax.random.normal(ks[1], (E,), jnp.float32),
          "w_in": jax.random.normal(ks[2], (E, D, F)) / D ** 0.5,
          "w_gate": jax.random.normal(ks[3], (E, D, F)) / D ** 0.5,
          "w_out": jax.random.normal(ks[4], (E, F, D)) / F ** 0.5}
    x = jax.random.normal(ks[5], (B, T, D), jnp.float32)
    cot = jax.random.normal(ks[6], (B, T, D), jnp.float32)
    return x, lp, cot


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of nested calls (pjit, custom
    derivative rules) included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _products(fn, x, lp):
    """Output shapes of the products against `w_in` and `w_gate` (an
    operand [E, D, F]) in fn's jaxpr; einsum orders their axes as it likes."""
    E = lp["w_in"].shape[0]
    return [e.outvars[0].aval.shape
            for e in _eqns(jax.make_jaxpr(fn)(x, lp).jaxpr)
            if e.primitive.name == "dot_general"
            and any(v.aval.shape == (E, D, F) for v in e.invars)]


def _primitives(fn, x, lp):
    return {e.primitive.name for e in _eqns(jax.make_jaxpr(fn)(x, lp).jaxpr)}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("gating", ["softmax", "sigmoid"])
def test_the_dropless_form_is_the_padded_forms_sum(gating, shape):
    """At a capacity that drops nothing: the same output and aux as the
    gather and the dense dispatch, the same gradients into x and the
    expert weights, and `_moe_ffn` takes it (no slots, no scatter)."""
    B, T = SHAPES[shape]
    cfg = _cfg(gating, capacity_factor=4.0)  # E / k: capacity == T
    assert tr.moe_capacity(cfg, T) >= T
    x, lp, cot = _layer(cfg, B, T)

    def scalar(form):
        def f(x, w):
            out, aux = form(x, {**lp, **w}, cfg)
            return jnp.sum(out * cot) + aux, (out, aux)
        return f

    w = {k: lp[k] for k in ("w_in", "w_gate", "w_out", "router")}
    got = {}
    for form in (tr._moe_ffn_dropless, tr._moe_ffn_gather, tr._moe_ffn_dense,
                 tr._moe_ffn):
        (_, (out, aux)), grads = jax.value_and_grad(
            scalar(form), argnums=(0, 1), has_aux=True)(x, w)
        got[form.__name__] = (out, aux, grads)
    out, aux, grads = got["_moe_ffn_dropless"]
    assert out.shape == x.shape and out.dtype == x.dtype
    assert float(jnp.abs(out).max()) > 0.1
    for other in ("_moe_ffn_gather", "_moe_ffn_dense", "_moe_ffn"):
        o_out, o_aux, o_grads = got[other]
        np.testing.assert_allclose(out, o_out, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(aux, o_aux, atol=1e-6, rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5),
            grads, o_grads)
    fn = lambda x, lp: tr._moe_ffn(x, lp, cfg)  # noqa: E731
    assert not {"scatter-add", "scatter", "cumsum"} & _primitives(fn, x, lp)
    np.testing.assert_array_equal(got["_moe_ffn"][0], out)


@pytest.mark.parametrize("gating", ["softmax", "sigmoid"])
def test_a_capacity_under_the_row_takes_the_gather_path_and_drops(gating):
    """`moe_capacity(cfg, T) < T`: `_moe_ffn` is the gather path, and a
    router that sends every token to one expert loses the tokens past the
    capacity, as `_moe_route` says, where the dropless form keeps them."""
    B, T = 2, 16
    cfg = _cfg(gating, capacity_factor=1.0)
    capacity = tr.moe_capacity(cfg, T)
    assert capacity < T
    x, lp, _ = _layer(cfg, B, T)
    # every token's first choice is expert 0 (softmax: a large logit;
    # sigmoid: the choice goes by score + bias)
    x = jnp.abs(x)
    lp["router"] = lp["router"].at[:, 0].set(1.0)
    lp["router_bias"] = lp["router_bias"].at[0].set(5.0)
    fn = lambda x, lp: tr._moe_ffn(x, lp, cfg)  # noqa: E731
    assert {"gather", "scatter-add", "cumsum"} <= _primitives(fn, x, lp)
    *_, keep, cap = tr._moe_route(x, lp, cfg)
    assert cap == capacity
    keep = np.asarray(keep).reshape(B, T, 2)
    assert keep[:, :capacity].all() and not keep[:, capacity:, 0].any()
    out, _ = tr._moe_ffn(x, lp, cfg)
    ref, _ = tr._moe_ffn_gather(x, lp, cfg)
    dense, _ = tr._moe_ffn_dense(x, lp, cfg)
    np.testing.assert_array_equal(out, ref)
    np.testing.assert_allclose(out, dense, atol=2e-5, rtol=2e-5)
    whole, _ = tr._moe_ffn_dropless(x, lp, cfg)
    np.testing.assert_allclose(out[:, :capacity], whole[:, :capacity],
                               atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(out[:, capacity:] - whole[:, capacity:]).max()) > 1e-2


@pytest.mark.parametrize("capacity_factor", [4.0, 1.25])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_rows_counted_are_the_rows_the_program_computes(
        shape, capacity_factor):
    """`moe_rows_computed` is the leading sizes of the three expert
    products in the program `_moe_ffn` lowers, whichever form it takes."""
    B, T = SHAPES[shape]
    cfg = _cfg("softmax", capacity_factor)
    x, lp, _ = _layer(cfg, B, T)
    products = _products(lambda x, lp: tr._moe_ffn(x, lp, cfg), x, lp)
    assert len(products) == 2  # w_in and w_gate; w_out contracts F
    E, capacity = cfg.num_experts, tr.moe_capacity(cfg, T)
    dropless = capacity >= T
    assert dropless == (shape == "decode" or capacity_factor == 4.0)
    want = E * B * T if dropless else B * E * capacity
    assert tr.moe_rows_computed(cfg, B, T) == want
    for s in products:
        assert F in s and int(np.prod(s)) // F == want, (s, want)
    # never more rows than the padded forms compute for the same program
    assert want <= B * E * capacity
