"""The LFM2 mixture-of-experts stack (`tiny-lfm2`: gated short convolutions
beside rotary GQA with normalised queries and keys, two dense layers and
then experts chosen by sigmoid score + bias) against the plain reference of
its family (benchmark/reference/lfm2.py: float32, `highest`, no kernel, no
cache, no capacity, nothing imported from the program), on seeded weights.

Tolerances. Weights are the family's bfloat16 draws cast to float32 and the
tiny model runs in float32, so program and reference differ only in the
order of float32 sums (CPU matmuls at default precision against `highest`,
one-pass against blockwise softmax, a carried convolution tail against one
pass over the sequence, experts' rows gathered against every expert over
every token): log-probabilities agree to LOGPROB_TOL, one mixer's output to
MIXER_TOL. The control rounds the same weights to fp8 and must land far
outside LOGPROB_TOL."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import lfm2 as ref
from benchmark.tests.tiny import tiny_spec
from ray_tpu.models import forward, get_config, init_params, stack
from ray_tpu.models.transformer import _qkv, moe_rows_computed
from ray_tpu.ops import mha_reference
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.rope import rope_frequencies
from ray_tpu.parallel.moe import sigmoid_bias_gating
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

# float32 on both sides: 2e-5 is 20x the largest difference seen over the
# cases below (9.5e-7, engine against reference); the fp8 control reads
# 4e-3 rms
LOGPROB_TOL = 2e-5
MIXER_TOL = 2e-6
PAGE = 4
CONV_GAIN = 8.0


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec("lfm2-8b-a1b")
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(32)))
    # At 64 wide B * x * C is a product of three numbers of about 0.16 and
    # hardly reaches the logits (a test of the tails and the slot's reset
    # would have no teeth); with the in-projection 8x larger, dropping the
    # carried tail moves a log-probability by 0.05.
    params["layers"] = [
        tuple({n: w * (CONV_GAIN if n == "c_in" else 1.0)
               for n, w in lp.items()} for lp in segment)
        for segment in params["layers"]]
    cfg = family.model_config(spec, dtype="float32")
    return spec, family, cfg, params


def engine_for(cfg, params, **kw):
    ecfg = dict(max_batch_size=2, page_size=PAGE, max_pages=96, max_seq_len=96,
                prefill_buckets=(8, 16), prefill_chunk=16, decode_span=4,
                busy_span=2, cache_dtype="float32")
    ecfg.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**ecfg))


def reference_logprobs(model, prompt, output, mode=None):
    """log-softmax of the reference's logits at the positions that predict
    `output`, in one cache-less pass over prompt + output."""
    spec, family, _, params = model
    seq = list(prompt) + list(output)
    padded = np.zeros((-(-len(seq) // family.PAD_TO) * family.PAD_TO,), np.int32)
    padded[:len(seq)] = seq
    at = len(prompt) - 1 + np.arange(len(output))
    logits = np.asarray(family.logits_at(params, jnp.asarray(padded),
                                         jnp.asarray(at), spec, mode), np.float64)
    return logits - np.log(np.exp(logits - logits.max(-1, keepdims=True))
                           .sum(-1, keepdims=True)) - logits.max(-1, keepdims=True)


def prompts(n, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 256, t).tolist() for t in lengths[:n]]


# -- the stack's shape -------------------------------------------------------


def test_two_dense_then_sparse_layers_scan_whole_periods(model):
    _, _, cfg, params = model
    assert cfg.layer_kinds == ("conv", "conv") + ("attn", "conv", "conv", "conv") * 2
    assert cfg.second_halves == ("ffn",) * 2 + ("moe",) * 8
    # a layer is a (mixer, second half) pair: the two dense conv layers are
    # one scan, the sparse periods another, though "conv" is in both
    assert cfg.segments() == ((0, ("conv",), 2),
                              (2, ("attn", "conv", "conv", "conv"), 2))
    assert cfg.cache_dims == (2, 4, 8) and cfg.conv_tail == (8, 2, 64)
    assert cfg.has_state
    dense, sparse = params["layers"]
    assert dense[0]["w_in"].shape == (2, 64, 128)          # [repeats, D, F]
    assert sparse[1]["w_in"].shape == (2, 8, 64, 32)       # [repeats, E, D, Fe]
    assert "router_bias" in sparse[0] and "router" not in dense[0]
    big = get_config("lfm2-8b-a1b")
    assert big.segments() == (
        (0, ("conv",), 2), (2, ("attn", "conv", "conv", "conv"), 4),
        (18, ("attn", "conv", "conv"), 2))
    assert round(big.param_count() / 1e9, 2) == 8.34
    tiny = get_config("tiny-lfm2")
    tree = init_params(tiny, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == tiny.param_count()


def test_kinds_that_would_need_two_row_shapes_are_refused():
    base = dict(name="x", vocab_size=8, d_model=16, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=16)
    from ray_tpu.models import StackConfig
    with pytest.raises(ValueError, match="two shapes of layers that cache"):
        StackConfig(**base, layer_kinds=("attn", "full"))
    with pytest.raises(ValueError, match="two shapes of convolution tails"):
        StackConfig(**base, layer_kinds=("conv", "mamba"))
    # the pairing rule belongs to the differential kinds, not to a stack
    odd = {**base, "n_heads": 3, "n_kv_heads": 3, "head_dim": 4}
    StackConfig(**odd, layer_kinds=("attn", "conv"))
    with pytest.raises(ValueError, match="pairs heads"):
        StackConfig(**odd, layer_kinds=("full", "cross"))


# -- the convolution mixer over the modes ------------------------------------


def _conv_layer(model):
    _, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][0][0])
    return cfg, lp, {n: lp[n] for n in ("c_in", "c_conv", "c_out")}


def _tables(B):
    return jnp.zeros((B, 4), jnp.int32)


@pytest.mark.parametrize("path", ["seq", "seq_then_decode", "chunks"])
def test_the_convolution_mixer_equals_the_whole_sequence(model, path):
    """One sequence of 23 positions through the mixer: whole (`Seq`); 9
    positions kept (`Seq` with `keep`, padded to 16) and then 14 `Decode`
    steps from the tail; three chunks of 8 from carried state, the last
    with 7 real positions. Row 3 of 8 tails: the row is the mode's."""
    cfg, lp, plain = _conv_layer(model)
    T, ci = 23, 3
    u = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))
    want = ref.short_conv(u[0], plain)
    state = stack.new_request_state(cfg, 1, jnp.float32)
    if path == "seq":
        got, _ = stack._short_conv(u, lp, cfg, ci, stack.Seq(cfg), {})
    elif path == "seq_then_decode":
        n, pad = 9, 16
        head = jnp.zeros((1, pad, cfg.d_model)).at[:, :n].set(u[:, :n])
        mode = stack.Seq(cfg, n_valid=jnp.array([n]), keep=True)
        first, carry = stack._short_conv(head, lp, cfg, ci, mode, dict(state))
        outs = [first[:, :n]]
        for t in range(n, T):
            mode = stack.Decode(cfg, jnp.array([t]), _tables(1), PAGE)
            o, carry = stack._short_conv(u[:, t:t + 1], lp, cfg, ci, mode, carry)
            outs.append(o)
        got = jnp.concatenate(outs, axis=1)
    else:
        C, carry, outs = 8, dict(state), []
        for start in range(0, T, C):
            n = min(C, T - start)
            chunk = jnp.zeros((1, C, cfg.d_model)).at[:, :n].set(
                u[:, start:start + n])
            mode = stack.Seq(cfg, n_valid=jnp.array([n]), keep=True,
                             chunk=(start, _tables(1)[0]), page_size=PAGE)
            o, carry = stack._short_conv(chunk, lp, cfg, ci, mode, carry)
            outs.append(o[:, :n])
        got = jnp.concatenate(outs, axis=1)
        # the other layers' tails were left alone
        assert not np.asarray(carry["conv"][:ci]).any()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=MIXER_TOL, rtol=0)


# -- the routing rule --------------------------------------------------------


def _route_spec(**kw):
    return {"num_experts_per_tok": 2, "use_expert_bias": True,
            "norm_topk_prob": True, "routed_scaling_factor": 1.0, **kw}


@pytest.mark.parametrize("case", ["bias_moves_the_choice", "norm_off",
                                  "scaled"])
def test_sigmoid_routing_against_the_reference(case):
    """Scores are sigmoids; the bias takes part in the choice and not in
    the weights; the weights are over their sum + 1e-6 unless
    `norm_topk_prob` is off; then the scaling factor."""
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.1, 0.2, 0.3, 0.4]])
    bias = jnp.array([0.0, 0.0, 0.0, 0.9])
    spec = _route_spec(norm_topk_prob=case != "norm_off",
                       routed_scaling_factor=2.5 if case == "scaled" else 1.0)
    # the reference takes f and a router; the identity makes f the logits
    lp = {"router": jnp.eye(4), "router_bias": bias}
    want_w, want_ids = ref.route(logits, lp, spec)
    w, ids = sigmoid_bias_gating(logits, bias, 2, spec["norm_topk_prob"],
                                 spec["routed_scaling_factor"])
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(w), np.asarray(want_w), atol=1e-7)
    s = np.asarray(jax.nn.sigmoid(logits))
    # row 0: without the bias experts 0 and 1; with it expert 3 (score
    # 0.27 + 0.9) takes the first place, and its weight is its SCORE's
    assert sorted(np.asarray(ids[0])) == [0, 3]
    _, unbiased = sigmoid_bias_gating(logits, 0 * bias, 2)
    assert sorted(np.asarray(unbiased[0])) == [0, 1]
    chosen = s[0, np.asarray(ids[0])]
    if case == "norm_off":
        np.testing.assert_allclose(np.asarray(w[0]), chosen, atol=1e-7)
        assert abs(float(w[0].sum()) - 1.0) > 0.1
    else:
        scale = spec["routed_scaling_factor"]
        np.testing.assert_allclose(
            np.asarray(w[0]), scale * chosen / (chosen.sum() + 1e-6), atol=1e-6)


def test_queries_and_keys_are_normalised_before_the_turn(model):
    """Per head over its own lanes, one weight vector for all heads, then
    the rotary turn at the token's position."""
    spec, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][1][0])  # an attn layer
    lp = {**lp, "q_norm": lp["q_norm"] * 1.5, "k_norm": lp["k_norm"] * 0.5}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, cfg.d_model))
    tables = rope_frequencies(cfg.hdim, cfg.max_seq_len, cfg.rope_theta)
    q, k, _ = _qkv(x, lp, cfg, tables, None)
    from benchmark.reference.model import rms_norm, rope
    want_q = rope(rms_norm(jnp.einsum("td,dhk->thk", x[0], lp["wq"]),
                           lp["q_norm"], cfg.norm_eps), cfg.rope_theta)
    want_k = rope(rms_norm(jnp.einsum("td,dhk->thk", x[0], lp["wk"]),
                           lp["k_norm"], cfg.norm_eps), cfg.rope_theta)
    np.testing.assert_allclose(np.asarray(q[0]), np.asarray(want_q), atol=MIXER_TOL)
    np.testing.assert_allclose(np.asarray(k[0]), np.asarray(want_k), atol=MIXER_TOL)
    # the one-block models leave the field off and have no such weights
    assert not get_config("tiny-llama").qk_norm


# -- the whole model ---------------------------------------------------------


def test_forward_agrees_with_the_plain_reference(model):
    spec, family, cfg, params = model
    tokens = np.asarray(prompts(1, [family.PAD_TO])[0], np.int32)
    got, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens[None])
    at = np.arange(len(tokens))
    want = family.logits_at(params, jnp.asarray(tokens), jnp.asarray(at), spec)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=LOGPROB_TOL, rtol=0)


@pytest.mark.parametrize("path,length", [
    ("bucket", 5),     # one bucket
    ("bucket", 16),    # a whole bucket
    ("chunked", 21),   # two chunks, the last one padded
    ("chunked", 48),   # three whole chunks
])
def test_prefill_and_decode_agree_with_the_plain_reference(model, path, length):
    """Both prefill paths, then 30 decoded tokens through pages (the 2
    attention layers of the 10) and convolution tails, against the
    reference's one cache-less pass, on log-probabilities."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    try:
        assert (length > eng.ecfg.prefill_chunk) == (path == "chunked")
        assert eng.k_pages.shape[0] == 2 and eng.state["conv"].shape == (8, 2, 2, 64)
        prompt = prompts(1, [length], seed=length)[0]
        out = eng.generate(prompt, max_tokens=30)
    finally:
        eng.stop()
    want = reference_logprobs(model, prompt, out["token_ids"])
    served = np.asarray(out["logprobs"])
    picked = want[np.arange(30), out["token_ids"]]
    assert np.abs(served - picked).max() < LOGPROB_TOL
    # greedy: the served token is the reference's best (or within rounding)
    assert (want.max(-1) - picked).max() < LOGPROB_TOL


def test_a_slot_is_reused_and_the_experts_rows_are_counted(model):
    """Three requests on two slots: the third takes the slot of whichever
    finishes first, so its tails must be its own (install overwrites them);
    and every dispatched program adds its expert rows to the two counters
    that `moe_rows_padding_factor` reads."""
    _, _, cfg, params = model
    before = common.counters()
    eng = engine_for(cfg, params)
    # (rows, row_tokens, live, times, experts the steps visited, tokens
    # the rows hold) of every dispatch; the last but one is None but for a
    # decode span, the last None but for a bucket or a chunk
    programs = []
    count = eng._count_moe_rows

    def counted(rows, row_tokens, live, times=1, touched=None, held=None):
        programs.append((rows, row_tokens, live, times, touched, held))
        count(rows, row_tokens, live, times, touched=touched, held=held)

    eng._count_moe_rows = counted
    try:
        ps = prompts(3, [11, 19, 7], seed=3)
        budgets = [6, 24, 26]
        reqs = [Request(request_id=f"r{i}", prompt=p, max_tokens=m)
                for i, (p, m) in enumerate(zip(ps, budgets))]
        for r in reqs:
            eng.add_request(r)
        for r in reqs:
            assert r.done.wait(300) and r.error is None
    finally:
        eng.stop()
    after = common.counters()
    assert common.counter_delta(before, after, "serve_state_slots_installed") >= 3
    computed = common.counter_delta(before, after, "serve_moe_rows_computed")
    routed = common.counter_delta(before, after, "serve_moe_rows_routed")
    # 8 expert layers, 2 experts a token: at least every prompt and output
    # token was routed, and every expert ran over every token: more rows
    assert routed >= 8 * 2 * (sum(map(len, ps)) + sum(budgets) - 3)
    assert computed > routed
    # the exact count: every program here is dropless (capacity_factor is
    # experts / k), so each of the 8 expert layers of a chunk (1 x 16) or a
    # bucket (rows x 8 or 16) ran each expert over the rows that chose it,
    # in passes whose rows the host bounds from the tokens the rows hold
    # (`moe_rows_computed(tokens=)`: never more than its 8 experts over
    # every row); a decode span of 2 slots x 1 ran the experts its steps
    # VISITED (those a live row chose: at least the 2 of one row, at most
    # the 4 of both, a step and layer) over its 2 rows
    assert {(r, t) for r, t, *_ in programs} >= {(2, 1), (1, 16)}
    spans = [p for p in programs if p[4] is not None]
    assert spans and all((r, t) == (2, 1) for r, t, *_ in spans)
    for _, _, live, times, touched, _ in spans:
        assert 8 * times * 2 * min(live, 1) <= touched <= 8 * times * 2 * live
    seqs = [p for p in programs if p[4] is None]
    assert seqs and all(held is not None and live <= held <= rows * t
                        for rows, t, live, _, _, held in seqs)
    assert computed == sum(
        times * 8 * moe_rows_computed(cfg, rows, row_tokens, tokens=held)
        for rows, row_tokens, _, times, _, held in seqs) + sum(
            2 * p[4] for p in spans)
    for rows, row_tokens, live, _, _, held in seqs:
        assert 2 * live <= moe_rows_computed(
            cfg, rows, row_tokens, tokens=held) <= 8 * rows * row_tokens
    assert routed == 8 * 2 * sum(
        times * live for _, _, live, times, *_ in programs)
    for r, p in zip(reqs, ps):
        want = reference_logprobs(model, p, r.output)
        picked = want[np.arange(len(r.output)), r.output]
        assert np.abs(np.asarray(r.output_logprobs) - picked).max() < LOGPROB_TOL


def test_a_lower_precision_than_stated_fails(model):
    """The control: the reference with fp8 weights in the program's place
    lands far outside the tolerance that the program meets."""
    prompt = prompts(1, [24], seed=9)[0]
    output = prompts(1, [24], seed=10)[0]
    exact = reference_logprobs(model, prompt, output)
    low = reference_logprobs(model, prompt, output, mode="fp8")
    at = np.arange(len(output))
    err = np.abs(low[at, output] - exact[at, output])
    assert np.sqrt(np.mean(err ** 2)) > 100 * LOGPROB_TOL


# -- heads of 64 on the kernels ----------------------------------------------

H, KVH, D, PS = 8, 4, 64, 4
TOL = 5e-6  # float32, same arithmetic in another order


@pytest.fixture
def pallas_everywhere(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


def _pool(key, layers, pages):
    return jax.random.normal(key, pa.pool_shape(layers, pages, PS, KVH, D))


@pytest.mark.parametrize("op", ["decode", "chunk", "verify", "flash"])
def test_heads_of_64_ride_the_kernels(pallas_everywhere, op):
    """Two neighbouring kv heads of 64 are one 128-lane tile of the pool's
    row and a query head is zero outside its own half: every paged op and
    the flash op run their Pallas kernel (interpret mode here) and agree
    with their XLA twin on the plain heads."""
    k = jax.random.split(jax.random.PRNGKey(64), 4)
    kp, vp = _pool(k[0], 2, 13), _pool(k[1], 2, 13)
    table = jnp.arange(1, 13, dtype=jnp.int32).reshape(2, 6)
    lengths = jnp.array([19, 7], jnp.int32)
    if op == "flash":
        T = 128
        q = jax.random.normal(k[2], (2, T, H, D))
        kk = jax.random.normal(k[0], (2, T, KVH, D))
        vv = jax.random.normal(k[1], (2, T, KVH, D))
        run = jax.jit(lambda q, kk, vv: flash_attention(q, kk, vv))
        assert "pallas_call" in str(jax.make_jaxpr(run)(q, kk, vv))
        got, want = run(q, kk, vv), mha_reference(q, kk, vv)
    elif op == "decode":
        q = jax.random.normal(k[2], (2, H, D))
        args = (q, kp, vp, table, lengths, 1)
        run = jax.jit(lambda *a: pa.paged_attention_decode(*a))
        assert "pallas_call" in str(jax.make_jaxpr(run)(*args))
        got = run(*args)
        want = pa.paged_attention_decode(*args, force_xla=True)
    elif op == "chunk":
        q = jax.random.normal(k[2], (8, H, D))
        args = (q, kp, vp, table[0], 8, 16, 1)
        run = jax.jit(lambda *a: pa.paged_attention_chunk(*a))
        assert "pallas_call" in str(jax.make_jaxpr(run)(*args))
        got = run(*args)
        want = pa.paged_attention_chunk(*args, force_xla=True)
    else:
        q = jax.random.normal(k[2], (2, 3, H, D))
        args = (q, kp, vp, table, lengths, 1)
        run = jax.jit(lambda *a: pa.paged_attention_verify(*a))
        assert "pallas_call" in str(jax.make_jaxpr(run)(*args))
        got = run(*args)
        want = pa.paged_attention_verify(*args, force_xla=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)
