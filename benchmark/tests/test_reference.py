"""The plain reference agrees with the program's forward at a tiny size on
the CPU, dense and dropless sparse, and the control (the reference in a
lower precision) is told apart by the numbers `correct` compares."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common, weights
from benchmark.reference import model as ref
from benchmark.tests.tiny import tiny_spec


def _params(spec, seed=2**31 + 3, gain=3.0):
    # a larger gain than the benchmark's 0.02 so that attention and routing
    # matter at width 64
    p = weights.make_weights(spec, seed)
    return jax.tree.map(lambda a: a.astype(jnp.float32) * gain, p)


@pytest.mark.parametrize("config", ["mistral-7b", "mixtral-8x7b"])
def test_reference_agrees_with_the_programs_forward(config):
    from ray_tpu.models import forward

    spec = tiny_spec(config)
    cfg = common.family(spec).model_config(spec, dtype="float32")
    params = _params(spec)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 48), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = forward(params, tokens[None], cfg)
    got = ref.logits_at(params, tokens, jnp.arange(48), spec)
    assert float(jnp.max(jnp.abs(logits[0] - got))) < 1e-4


def test_sparse_reference_is_dropless_and_top2():
    spec = tiny_spec("mixtral-8x7b")
    cfg = common.family(spec).model_config(spec, dtype="float32")
    assert cfg.capacity_factor == 4.0  # experts / selected: capacity == T
    lp = jax.tree.map(lambda a: a[0], _params(spec)["layers"])
    # every token the same: all route to the same two experts, the case a
    # capacity below T would drop
    x = jnp.tile(jnp.linspace(-1, 1, 64)[None], (32, 1))
    y = ref.sparse_ffn(x, lp, spec)
    top, ids = jax.lax.top_k(x[0] @ lp["router"], 2)
    w = jax.nn.softmax(top)
    want = sum(w[i] * ref.dense_ffn(x[:1], lp["w_in"][ids[i]],
                                    lp["w_gate"][ids[i]], lp["w_out"][ids[i]])
               for i in range(2))
    assert float(jnp.max(jnp.abs(y - want))) < 1e-5  # every row, none dropped


@pytest.mark.parametrize("config", ["mistral-7b", "mixtral-8x7b"])
@pytest.mark.parametrize("mode", ["int8", "fp8", "kv-int8", "kv-fp8"])
def test_control_is_told_apart_serve(config, mode):
    """The serve numbers of the control lie far above those of a sound
    bf16-rounded run of the same reference. An int8 cache with a scale per
    token and head errs little more than bfloat16 does (1.5x here), so of
    it the test asks only that it reads above the sound run; what it reads
    at a cell's own size is in PERF.md, section 2."""
    spec = tiny_spec(config)
    params = _params(spec)
    rng = np.random.default_rng(1)
    prompt, output = rng.integers(3, 256, 40), rng.integers(3, 256, 12)

    def numbers(logits):
        logits = np.asarray(logits, np.float64)
        tokens = logits.argmax(-1)
        lp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
        return checks.reduce_serve([checks.serve_numbers(
            exact, tokens, lp[np.arange(len(tokens)), tokens])])

    exact = checks.reference_logits(params, spec, prompt, output)
    bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    sound = numbers(checks.reference_logits(bf16, spec, prompt, output))
    control = numbers(checks.reference_logits(params, spec, prompt, output, mode))
    factor = 1.0 if mode == "kv-int8" else 2.0
    assert control["logprob_rms_err"] > factor * sound["logprob_rms_err"]
    assert control["logprob_rms_err"] > 1e-3


def test_control_is_told_apart_train():
    spec = tiny_spec("mistral-7b")
    params = _params(spec)
    row = jnp.asarray(np.random.default_rng(2).integers(3, 256, 129), jnp.int32)
    exact = ref.nll_and_norm_grads(params, row[:-1], row[1:], spec)
    control = ref.nll_and_norm_grads(params, row[:-1], row[1:], spec, "fp8")
    n = checks.train_numbers(*control, *exact)
    assert n["nll_rms_err"] > 1e-3 and n["grad_rel_err"] > 1e-2
    same = checks.train_numbers(*exact, *exact)
    assert same == {"nll_rms_err": 0.0, "grad_rel_err": 0.0}


def test_program_probe_agrees_with_reference_gradients():
    spec = tiny_spec("mistral-7b")
    family = common.family(spec)
    cfg = family.model_config(spec, dtype="float32")
    params = _params(spec)
    row = jnp.asarray(np.random.default_rng(3).integers(3, 256, 129), jnp.int32)
    with jax.default_matmul_precision("highest"):
        nll, g = family.program_probe(cfg, params, row[:-1], row[1:])
    ref_nll, ref_g = ref.nll_and_norm_grads(params, row[:-1], row[1:], spec)
    n = checks.train_numbers(nll, g, ref_nll, ref_g)
    assert n["nll_rms_err"] < 1e-4 and n["grad_rel_err"] < 1e-3
