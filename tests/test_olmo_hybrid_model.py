"""The Olmo Hybrid stack (`tiny-olmo-hybrid`: three layers of gated
delta-rule linear attention to one of full attention without positions,
norms after their sublayers, an untied head) against the plain reference of
its family (benchmark/reference/olmo_hybrid.py: float32, `highest`, no
kernel, no cache, the recurrence a plain scan, nothing imported from the
program), on seeded weights; and the two delta-rule ops (ops/gdn.py), XLA
path and Pallas kernels in interpret mode, against the plain recurrence.

Tolerances. Weights are the family's bfloat16 draws cast to float32 and the
tiny model runs in float32, so program and reference differ only in the
order of float32 sums (CPU matmuls at default precision against `highest`,
one-pass against blockwise softmax, a carried tail and a carried state
matrix against one pass over the sequence): log-probabilities agree to
LOGPROB_TOL, the ops to OP_TOL. The controls (a bfloat16 state, int8
weights) must land far outside LOGPROB_TOL."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import olmo_hybrid as ref
from benchmark.tests.tiny import tiny_spec
from ray_tpu.models import StackConfig, forward, get_config, init_params, stack
from ray_tpu.models.transformer import _qkv
from ray_tpu.ops import gdn
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

# float32 on both sides: the largest differences seen over the cases below
# are 7.5e-6 (engine against reference, chunked prompt of 48) and 2.7e-5
# (one of 65536 logits of a 256-position forward pass: the norms FOLLOW the
# sublayers, so a layer's rounding is rescaled to unit size, and the
# recurrence sums 256 steps in another order); the bfloat16 state control
# reads 0.048 rms and the int8 weight control 0.144
LOGPROB_TOL = 1e-4
OP_TOL = 5e-6
PAGE = 4


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec("olmo-hybrid-7b")
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(34)))
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


def test_three_linear_layers_and_one_full_scan_as_whole_periods(model):
    _, _, cfg, params = model
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attn") * 2
    # ONE scan of two periods, not a scan of three and a layer alone, twice
    assert cfg.segments() == ((0, ("gdn", "gdn", "gdn", "attn"), 2),)
    assert cfg.cache_dims == (2, 4, 16)
    assert cfg.conv_tail == (6, 3, 4 * (8 + 8 + 16))   # q, k and v channels
    assert cfg.gdn_dims == (6, 4, 8, 16)
    assert cfg.has_state and cfg.post_norm and cfg.qk_norm_whole
    assert cfg.positional == "none" and not cfg.tie_embeddings
    (period,) = params["layers"]
    assert period[0]["d_in"].shape == (2, 64, 128)      # [repeats, D, q+k+v]
    assert period[3]["q_norm"].shape == (2, 4, 16)      # the whole vector
    assert params["lm_head"].shape == (64, 256)
    big = get_config("olmo-hybrid-7b")
    assert big.segments() == ((0, ("gdn", "gdn", "gdn", "attn"), 8),)
    assert round(big.param_count() / 1e9, 2) == 7.43    # the issue's count
    assert big.conv_tail == (24, 3, 11520) and big.cache_dims == (8, 30, 128)
    assert gdn.state_shape(*big.gdn_dims[:1], 64, *big.gdn_dims[1:]) == (
        24, 64, 96, 30 * 192)
    tiny = get_config("tiny-olmo-hybrid")
    tree = init_params(tiny, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == tiny.param_count()


def test_one_array_of_tails_holds_for_the_new_kind():
    base = dict(name="x", vocab_size=8, d_model=16, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=16, gdn_heads=2, gdn_key_dim=8,
                gdn_value_dim=8)
    for other in ("conv", "mamba"):
        with pytest.raises(ValueError, match="two shapes of convolution tails"):
            StackConfig(**base, layer_kinds=("gdn", other))
    with pytest.raises(ValueError, match="gdn_heads"):
        StackConfig(**{**base, "gdn_heads": 0}, layer_kinds=("gdn", "attn"))
    StackConfig(**base, layer_kinds=("gdn", "attn"))


# -- the two ops against the plain recurrence --------------------------------


def _operands(B, T, H, dk, dv, seed=0, beta_lo=0.0):
    """q, k normalised as the mixer hands them over; g <= 0; beta in
    (beta_lo, 2); a carried state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (B, T, H)))
    beta = beta_lo + (2 - beta_lo) * jax.nn.sigmoid(
        jax.random.normal(ks[4], (B, T, H)))
    s0 = jax.random.normal(ks[5], (B, dk, H * dv))
    return q, k, v, g, beta, s0


def _plain(q, k, v, g, beta, s0):
    """The reference's own scan, one sequence at a time, from s0."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    outs, ends = [], []
    for b in range(B):
        S = np.asarray(s0[b], np.float64).reshape(dk, H, dv).transpose(1, 0, 2)
        o = np.zeros((T, H, dv))
        for t in range(T):
            S = np.exp(np.asarray(g[b, t], np.float64))[:, None, None] * S
            kt = np.asarray(k[b, t], np.float64)
            d = np.asarray(beta[b, t], np.float64)[:, None] * (
                np.asarray(v[b, t], np.float64) - np.einsum("hij,hi->hj", S, kt))
            S = S + kt[:, :, None] * d[:, None, :]
            o[t] = np.einsum("hij,hi->hj", S, np.asarray(q[b, t], np.float64))
        outs.append(o)
        ends.append(S.transpose(1, 0, 2).reshape(dk, H * dv))
    return np.stack(outs), np.stack(ends)


@pytest.fixture
def pallas_everywhere(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("case", ["ragged", "beta_over_one", "wide_value"])
def test_the_chunk_op_is_the_plain_recurrence(request, path, case):
    """Lengths that are no multiple of the kernel's block of 64 (the rest
    is padding: g = beta = 0 there), from a carried state that is not zero,
    beta in (1, 2) where a step's eigenvalue is negative, and the published
    value width 192 (two heads a 384-lane unit in the step kernel)."""
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    B, T, H, dk, dv = (1, 64, 2, 96, 192) if case == "wide_value" \
        else (2, 128, 2, 16, 64)
    q, k, v, g, beta, s0 = _operands(
        B, T, H, dk, dv, seed=3, beta_lo=1.0 if case == "beta_over_one" else 0.0)
    n = np.array([T - 37, T][:B])
    valid = jnp.asarray(np.arange(T)[None, :] < n[:, None])[..., None]
    g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    run = jax.jit(lambda *a: gdn.gdn_chunk(*a, force_xla=path == "xla"))
    assert ("pallas_call" in str(jax.make_jaxpr(run)(q, k, v, g, beta, s0))) \
        == (path == "pallas")
    o, s1 = run(q, k, v, g, beta, s0)
    want_o, want_s = _plain(q, k, v, g, beta, s0)
    mask = np.asarray(valid)[..., None]
    assert np.abs(np.where(mask, np.asarray(o) - want_o, 0)).max() < OP_TOL
    # the padded positions left the state as the last real one did
    assert np.abs(np.asarray(s1) - want_s).max() < OP_TOL


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_prompt_fed_in_chunks_equals_the_same_prompt_whole(request, path):
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    q, k, v, g, beta, s0 = _operands(1, 192, 2, 16, 64, seed=5)
    force = path == "xla"
    whole_o, whole_s = gdn.gdn_chunk(q, k, v, g, beta, s0, force_xla=force)
    s, outs = s0, []
    for a in range(0, 192, 64):
        o, s = gdn.gdn_chunk(*(x[:, a:a + 64] for x in (q, k, v, g, beta)), s,
                             force_xla=force)
        outs.append(o)
    assert np.abs(np.asarray(jnp.concatenate(outs, 1) - whole_o)).max() < OP_TOL
    assert np.abs(np.asarray(s - whole_s)).max() < OP_TOL


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("live", [(True, False, True, False), (False,) * 4,
                                  (False, False, True, True), (True,) * 4])
def test_a_step_advances_live_slots_and_leaves_the_others_bit_for_bit(
        request, path, live):
    """One layer of the whole state array, in place; an empty slot's
    program moves nothing, whichever slots are live (the kernel's blocks
    lean on the nearest live slot's: first, last, none)."""
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    B, H, dk, dv = 4, 2, 16, 192
    q, k, v, g, beta, s0 = _operands(B, 1, H, dk, dv, seed=7, beta_lo=1.0)
    state = jnp.stack([s0 * 0.5, s0, s0 * 2.0])
    lv = jnp.asarray(live)
    run = jax.jit(lambda st, *a: gdn.gdn_step(st, 1, *a,
                                              force_xla=path == "xla"))
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], lv)
    assert ("pallas_call" in str(jax.make_jaxpr(run)(state, *args))) \
        == (path == "pallas")
    o, new = run(state, *args)
    want_o, want_s = _plain(q, k, v, g, beta, s0)
    on = np.asarray(live)
    assert np.abs(np.asarray(o)[on] - want_o[on, 0]).max(initial=0) < OP_TOL
    assert np.abs(np.asarray(new[1])[on] - want_s[on]).max(initial=0) < OP_TOL
    assert (np.asarray(new[1])[~on] == np.asarray(state[1])[~on]).all()
    assert (np.asarray(new[0]) == np.asarray(state[0])).all()
    assert (np.asarray(new[2]) == np.asarray(state[2])).all()


# -- the mixers over the modes -----------------------------------------------


def test_queries_and_keys_are_normalised_over_the_whole_vector(model):
    """One RMS over all the heads' lanes of a token, a weight a lane; no
    turn: nothing encodes positions."""
    spec, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][0][3])  # an attn layer
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, cfg.d_model))
    q, k, _ = _qkv(x, lp, cfg, None, None)
    from benchmark.reference.model import rms_norm
    for got, w, n in ((q, "wq", "q_norm"), (k, "wk", "k_norm")):
        flat = jnp.einsum("td,dhk->thk", x[0], lp[w]).reshape(12, -1)
        want = rms_norm(flat, lp[n].reshape(-1), cfg.norm_eps)
        np.testing.assert_allclose(np.asarray(got[0]).reshape(12, -1),
                                   np.asarray(want), atol=OP_TOL)


@pytest.mark.parametrize("path", ["seq", "seq_then_decode", "chunks"])
def test_the_delta_rule_mixer_equals_the_whole_sequence(model, path):
    """One sequence of 23 positions through the mixer: whole (`Seq`); 9
    positions kept (`Seq` with `keep`, padded to 16) and then 14 `Decode`
    steps from the tail and the state matrix; three chunks of 8 from
    carried state, the last with 7 real positions. Row 4 of 6."""
    spec, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][0][1])
    plain = {n: w for n, w in lp.items() if n.startswith("d_")}
    T, gi = 23, 4
    u = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.linear_attention(u[0], plain, spec)
    state = stack.new_request_state(cfg, 1, jnp.float32)
    tables = jnp.ones((1, 4), jnp.int32)  # page 1: a live slot
    if path == "seq":
        got, _ = stack._gdn(u, lp, cfg, gi, stack.Seq(cfg), {})
    elif path == "seq_then_decode":
        n, pad = 9, 16
        head = jnp.zeros((1, pad, cfg.d_model)).at[:, :n].set(u[:, :n])
        mode = stack.Seq(cfg, n_valid=jnp.array([n]), keep=True)
        first, carry = stack._gdn(head, lp, cfg, gi, mode, dict(state))
        outs = [first[:, :n]]
        for t in range(n, T):
            mode = stack.Decode(cfg, jnp.array([t]), tables, PAGE)
            o, carry = stack._gdn(u[:, t:t + 1], lp, cfg, gi, mode, carry)
            outs.append(o)
        got = jnp.concatenate(outs, axis=1)
    else:
        C, carry, outs = 8, dict(state), []
        for start in range(0, T, C):
            n = min(C, T - start)
            chunk = jnp.zeros((1, C, cfg.d_model)).at[:, :n].set(
                u[:, start:start + n])
            mode = stack.Seq(cfg, n_valid=jnp.array([n]), keep=True,
                             chunk=(start, tables[0]), page_size=PAGE)
            o, carry = stack._gdn(chunk, lp, cfg, gi, mode, carry)
            outs.append(o[:, :n])
        got = jnp.concatenate(outs, axis=1)
        # the other layers' tails and state were left alone
        assert not np.asarray(carry["conv"][:gi]).any()
        assert not np.asarray(carry["gdn"][:gi]).any()
        assert np.asarray(carry["gdn"][gi]).any()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=OP_TOL, rtol=0)


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
    attention layers of the 8), convolution tails and state matrices,
    against the reference's one cache-less pass, on log-probabilities."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    try:
        assert (length > eng.ecfg.prefill_chunk) == (path == "chunked")
        assert eng.k_pages.shape == (2, 1, 96, PAGE, 4 * 16)
        assert eng.state["conv"].shape == (6, 2, 3, 128)
        assert eng.state["gdn"].shape == (6, 2, 8, 4 * 16)
        assert eng.state["gdn"].dtype == jnp.float32
        assert eng.prefix is None  # off by derivation: state beside pages
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


def test_a_reused_slot_holds_nothing_of_its_last_occupant(model):
    """Three requests on two slots: the third takes the slot of whichever
    finishes first, so its tails and state matrices must be its own
    (install overwrites them); and every decode dispatch adds to the
    counter pair that `recurrent_state_live_share` reads."""
    _, _, cfg, params = model
    before = common.counters()
    eng = engine_for(cfg, params)
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
    live = common.counter_delta(before, after,
                                "serve_recurrent_state_slot_steps", state="live")
    held = common.counter_delta(before, after,
                                "serve_recurrent_state_slot_steps", state="held")
    active = common.counter_delta(before, after, "serve_decode_slot_steps",
                                  state="active")
    empty = common.counter_delta(before, after, "serve_decode_slot_steps",
                                 state="empty")
    assert live == active > 0 and held == active + empty and held % 2 == 0
    assert 0 < common.load_reader("recurrent_state_live_share")(
        {"counters": (before, after)}) <= 100
    for r, p in zip(reqs, ps):
        want = reference_logprobs(model, p, r.output)
        picked = want[np.arange(len(r.output)), r.output]
        assert np.abs(np.asarray(r.output_logprobs) - picked).max() < LOGPROB_TOL


def test_an_empty_slots_state_is_untouched_by_a_decode_step(model):
    """The decode program over two slots of which one holds a sequence:
    the other's delta-rule state comes back bit for bit (its table starts
    at the trash page), though its conv tail, like every slot's, shifts."""
    _, _, cfg, params = model
    key = jax.random.PRNGKey(8)
    state = stack.new_engine_state(cfg, 2, PAGE, jnp.float32, jnp.float32)
    state = {**state, "gdn": jax.random.normal(key, state["gdn"].shape)}
    pool = jnp.zeros(stack.pool_shape(2, 8, PAGE, 4, 16), jnp.float32)
    tables = jnp.array([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    mode = stack.Decode(cfg, jnp.array([5, 0]), tables, PAGE)
    _, _, _, new = jax.jit(lambda p, t, pools, st: stack.run_paged(
        p, t, cfg, mode, pools, st))(
            params, jnp.array([[7], [0]], jnp.int32), (pool, pool), state)
    old, got = np.asarray(state["gdn"]), np.asarray(new["gdn"])
    assert (got[:, 1] == old[:, 1]).all()
    assert (got[:, 0] != old[:, 0]).any()


def test_what_assumes_pages_are_the_whole_state_is_refused(model):
    _, _, cfg, params = model
    with pytest.raises(ValueError, match="delta-rule state"):
        engine_for(cfg, params, speculation={"mode": "ngram",
                                             "num_speculative_tokens": 2})
    with pytest.raises(ValueError, match="no sharding rules"):
        InferenceEngine(params, cfg, EngineConfig(max_pages=8), mesh=object())
    eng = engine_for(cfg, params)
    try:
        with pytest.raises(ValueError, match="delta-rule state matrices"):
            eng._refuse_kv_transfer("export_kv_pages")
    finally:
        eng.stop()


@pytest.mark.parametrize("mode", ["state-bf16", "int8"])
def test_a_lower_precision_than_stated_fails(model, mode):
    """The control separates: the reference with a bfloat16 delta-rule
    state, or with int8 weights, in the program's place differs from itself
    by far more than the rounding of a sound run."""
    prompt = prompts(1, [24], seed=9)[0]
    output = prompts(1, [24], seed=10)[0]
    exact = reference_logprobs(model, prompt, output)
    low = reference_logprobs(model, prompt, output, mode=mode)
    at = np.arange(len(output))
    err = np.abs(low[at, output] - exact[at, output])
    assert np.sqrt(np.mean(err ** 2)) > 50 * LOGPROB_TOL


# -- one query head a kv head on the decode kernel ---------------------------


@pytest.mark.parametrize("window", [None, 8])
def test_one_query_head_a_kv_head_takes_a_page_head_by_head(
        pallas_everywhere, window):
    """At group size 1 the decode kernel meets kv head c's 128-lane slice
    of a block with query row c alone (as it meets a grouped model's with
    the group's rows, since PR 35): the same numbers as the XLA twin."""
    from ray_tpu.ops import paged_attention as pa

    H, D, ps = 4, 128, 4
    k = jax.random.split(jax.random.PRNGKey(30), 3)
    kp = jax.random.normal(k[0], pa.pool_shape(2, 13, ps, H, D))
    vp = jax.random.normal(k[1], pa.pool_shape(2, 13, ps, H, D))
    q = jax.random.normal(k[2], (3, H, D))
    table = jnp.arange(1, 13, dtype=jnp.int32).reshape(3, 4)
    if window is not None:  # a ring of window / page + 1 pages
        table = table[:, :3]
    lengths = jnp.array([15, 1, 9], jnp.int32)
    args = (q, kp, vp, table, lengths, 1)
    run = jax.jit(lambda *a: pa.paged_attention_decode(*a, window=window))
    assert "pallas_call" in str(jax.make_jaxpr(run)(*args))
    want = pa.paged_attention_decode(*args, window=window, force_xla=True)
    np.testing.assert_allclose(np.asarray(run(*args)), np.asarray(want),
                               atol=OP_TOL)
