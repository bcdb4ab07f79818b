"""The Granite 4.0-H stack (Mamba-2 layers with a scalar decay a head beside
GQA layers without positions, four scalars, a tied table) against the plain
reference of its family (benchmark/reference/granite_hybrid.py: float32,
`highest`, no kernel, no cache, the recurrence a plain scan, nothing
imported from the program), on seeded weights; and the two state-space ops
(ops/ssd.py), XLA form and Pallas kernels in interpret mode, against the
sequential recurrence.

Weights and tolerances. The weights are the family's bfloat16 draws cast to
float32: its recipe (families/ssd_hybrid.py `init_weights`) scales by the
fan-in, so at 8 layers of width 64 as at 40 of 2048 a sublayer adds about
what it is given, a step of the state-space layers is 0.05 to 1 and the
attention layer's scores differ: the mixers, not the embedding times 12,
make the stream, and the test SEES them. The tiny model runs in float32, so
program and reference differ only in the order of float32 sums (CPU matmuls
at default precision against `highest`, the dual form's blocks against a
token at a time, a carried tail and state against one pass), which 16
sublayers that each add a unit to the stream carry on: logits (of size 0.13,
the largest 0.9: they are divided by 8) agree to LOGIT_TOL, the largest
difference seen 2.7e-6 over three seeds of weights; log-probabilities (of
size 5, whose float32 spacing is 4.8e-7) to LOGPROB_TOL, the largest seen
4.9e-7, one spacing. The controls read far above both: a bfloat16 state
1.0e-3 to 1.8e-3, int8 weights 1.4e-2 to 1.7e-2, and each of the four
scalars put back to its default 0.18 (the attention multiplier) to 5."""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import granite_hybrid as ref
from benchmark.tests.tiny import tiny_spec
from ray_tpu.models import StackConfig, forward, get_config, init_params, stack
from ray_tpu.ops import ssd
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

CONFIG = "granite-4.0-h-micro"
CELL = CONFIG + ".serve-chat-burst"
LOGIT_TOL = 1e-5
LOGPROB_TOL = 2e-6
# relative to the largest output: the dual form sums a block's 64 to 256
# terms in another order than a token at a time
OP_TOL = 5e-5
PAGE = 4

CATALOG_CONFIG = {  # the catalog row's `config`, key for key
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": [("attention" if l % 10 == 5 else "mamba")
                    for l in range(40)],
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec(CONFIG)
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(45)))
    cfg = family.model_config(spec, dtype="float32")
    return spec, family, cfg, params


def engine_for(cfg, params, **kw):
    ecfg = dict(max_batch_size=2, page_size=PAGE, max_pages=96, max_seq_len=96,
                prefill_buckets=(8, 16), prefill_chunk=16, decode_span=4,
                busy_span=2, cache_dtype="float32")
    ecfg.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**ecfg))


def reference_logits(model, prompt, output, mode=None):
    """The reference's logits at the positions that predict `output`, in
    one cache-less pass over prompt + output."""
    spec, family, _, params = model
    seq = list(prompt) + list(output)
    padded = np.zeros((-(-len(seq) // family.PAD_TO) * family.PAD_TO,), np.int32)
    padded[:len(seq)] = seq
    at = len(prompt) - 1 + np.arange(len(output))
    return np.asarray(family.logits_at(params, jnp.asarray(padded),
                                       jnp.asarray(at), spec, mode),
                      np.float64)


def log_softmax(logits):
    top = logits.max(-1, keepdims=True)
    return logits - top - np.log(np.exp(logits - top).sum(-1, keepdims=True))


def prompts(n, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 256, t).tolist() for t in lengths[:n]]


# -- the stack's shape -------------------------------------------------------


def test_runs_of_state_space_layers_scan_and_attention_stands_alone(model):
    _, _, cfg, params = model
    assert cfg.layer_kinds == ("ssd",) * 5 + ("attn",) + ("ssd",) * 2
    assert cfg.segments() == ((0, ("ssd",), 5), (5, ("attn",), 1),
                              (6, ("ssd",), 2))
    assert cfg.cache_dims == (1, 2, 8)
    assert cfg.conv_tail == (7, 3, 128 + 2 * 2 * 16)   # x, and B and C a group
    assert cfg.ssd_dims == (7, 8, 16, 16, 2)
    assert cfg.has_state and cfg.positional == "none" and cfg.tie_embeddings
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
                12.0, 0.015625, 0.22, 8.0)
    first = params["layers"][0][0]
    assert first["s_in"].shape == (5, 64, 128 + 192)   # [repeats, D, z + xBC]
    assert first["s_dt"].shape == (5, 64, 8) and first["s_D"].shape == (5, 8)
    assert "lm_head" not in params
    big = get_config(CONFIG)
    assert [s[2] for s in big.segments()] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert round(big.param_count() / 1e6) == 3191       # the issue's count
    assert big.conv_tail == (36, 3, 4352) and big.cache_dims == (4, 8, 64)
    assert ssd.state_shape(big.ssd_dims[0], 64, *big.ssd_dims[1:4]) == (
        36, 64, 128, 4096)
    tiny = get_config("tiny-granite-hybrid")
    tree = init_params(tiny, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == tiny.param_count()
    # a plain model's scalars are the ones that emit nothing
    plain = get_config("tiny-llama")
    assert (plain.embedding_multiplier, plain.attention_multiplier,
            plain.residual_multiplier, plain.logits_scaling) == (
                1.0, None, 1.0, 1.0)


def test_one_array_of_tails_holds_for_the_new_kind():
    base = dict(name="x", vocab_size=8, d_model=16, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=16, ssm_inner=32, ssm_heads=4,
                gdn_heads=2, gdn_key_dim=8, gdn_value_dim=8)
    for other in ("conv", "mamba", "gdn"):
        with pytest.raises(ValueError, match="two shapes of convolution tails"):
            StackConfig(**base, layer_kinds=("ssd", other))
    with pytest.raises(ValueError, match="ssm_heads"):
        StackConfig(**{**base, "ssm_heads": 0}, layer_kinds=("ssd", "attn"))
    with pytest.raises(ValueError, match="ssm_groups"):
        StackConfig(**base, ssm_groups=3, layer_kinds=("ssd", "attn"))
    StackConfig(**base, layer_kinds=("ssd", "attn"))


def test_the_configurations_file_is_the_catalogs_row_uncut():
    spec = common.load_json("configs", CONFIG + ".json")
    assert {k: spec[k] for k in CATALOG_CONFIG} == CATALOG_CONFIG
    assert spec["reduced"] == {} and spec["published"]["num_hidden_layers"] == 40
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == [] and sorted(entry) == [
        "file", "name", "reduced", "source", "why"]
    assert entry["source"] == spec["source"] and spec["source"].endswith(
        "ibm-granite/granite-4.0-h-micro/blob/main/config.json")
    cfg = common.family(spec).model_config(spec)
    assert cfg.param_count() == get_config(CONFIG).param_count()
    assert dataclasses.replace(cfg, name=CONFIG) == get_config(CONFIG)
    assert (cfg.count("ssd"), cfg.count("attn")) == (36, 4)
    assert [l for l, k in enumerate(cfg.layer_kinds) if k == "attn"] == [
        5, 15, 25, 35]


# -- the two ops against the sequential recurrence ---------------------------


def _operands(B, T, H, P, G, N, seed=0):
    """x, a step dt that reaches past 1 (nothing clamps it), A in
    [-16, -1], B and C, and a carried state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) * 1.5 - 1.0)
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    s0 = jax.random.normal(ks[5], (B, N, H * P))
    return x, dt, A, Bm, Cm, s0


def _plain(x, dt, A, Bm, Cm, s0):
    """The recurrence a token at a time, in float64, from s0."""
    x, dt, A, Bm, Cm, s0 = (np.asarray(a, np.float64)
                            for a in (x, dt, A, Bm, Cm, s0))
    B, T, H, P = x.shape
    N, R = Bm.shape[-1], H // Bm.shape[2]
    S = s0.reshape(B, N, H, P).copy()
    y = np.zeros((B, T, H, P))
    for t in range(T):
        b_h, c_h = (np.repeat(m[:, t], R, axis=1) for m in (Bm, Cm))
        S = (np.exp(dt[:, t] * A)[:, None, :, None] * S
             + np.einsum("bhn,bhp->bnhp", b_h, dt[:, t][..., None] * x[:, t]))
        y[:, t] = np.einsum("bnhp,bhn->bhp", S, c_h)
    return y, S.reshape(B, N, H * P)


@pytest.fixture
def pallas_everywhere(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("case", ["ragged", "groups", "a_chunk_of_256"])
def test_the_chunk_op_is_the_sequential_recurrence(request, path, case):
    """The dual form from a carried state that is not zero: sequences whose
    ends are padding (dt = 0 there), two groups of heads with their own B
    and C, and the engine's chunk of 256 as ONE block at the published head
    and state sizes."""
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    B, T, H, P, G, N = {"ragged": (2, 128, 4, 64, 1, 128),
                        "groups": (1, 64, 4, 16, 2, 16),
                        "a_chunk_of_256": (1, 256, 2, 64, 1, 128)}[case]
    x, dt, A, Bm, Cm, s0 = _operands(B, T, H, P, G, N, seed=3)
    n = np.array([T - 37, T][:B])
    valid = jnp.asarray(np.arange(T)[None, :] < n[:, None])[..., None]
    dt = jnp.where(valid, dt, 0.0)
    run = jax.jit(lambda *a: ssd.ssd_chunk(*a, force_xla=path == "xla"))
    assert ("pallas_call" in str(jax.make_jaxpr(run)(x, dt, A, Bm, Cm, s0))) \
        == (path == "pallas")
    y, s1 = run(x, dt, A, Bm, Cm, s0)
    want_y, want_s = _plain(x, dt, A, Bm, Cm, s0)
    mask = np.asarray(valid)[..., None]
    assert np.abs(np.where(mask, np.asarray(y) - want_y, 0)).max() \
        < OP_TOL * np.abs(want_y).max()
    # the padded positions left the state as the last real one did
    assert np.abs(np.asarray(s1) - want_s).max() < OP_TOL * np.abs(want_s).max()


def test_the_xla_form_takes_any_length():
    x, dt, A, Bm, Cm, s0 = _operands(2, 23, 4, 16, 2, 16, seed=4)
    y, s1 = ssd.ssd_chunk(x, dt, A, Bm, Cm, s0)
    want_y, want_s = _plain(x, dt, A, Bm, Cm, s0)
    assert np.abs(np.asarray(y) - want_y).max() < OP_TOL * np.abs(want_y).max()
    assert np.abs(np.asarray(s1) - want_s).max() < OP_TOL * np.abs(want_s).max()


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_prompt_fed_in_chunks_equals_the_same_prompt_whole(request, path):
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    x, dt, A, Bm, Cm, s0 = _operands(1, 192, 2, 16, 1, 16, seed=5)
    force = path == "xla"
    whole_y, whole_s = ssd.ssd_chunk(x, dt, A, Bm, Cm, s0, force_xla=force)
    s, outs = s0, []
    for a in range(0, 192, 64):
        y, s = ssd.ssd_chunk(x[:, a:a + 64], dt[:, a:a + 64], A,
                             Bm[:, a:a + 64], Cm[:, a:a + 64], s,
                             force_xla=force)
        outs.append(y)
    scale = np.abs(np.asarray(whole_y)).max()
    assert np.abs(np.asarray(jnp.concatenate(outs, 1) - whole_y)).max() \
        < OP_TOL * scale
    assert np.abs(np.asarray(s - whole_s)).max() < OP_TOL * scale


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("live", [(True, False, True, False), (False,) * 4,
                                  (False, False, True, True), (True,) * 4])
def test_a_step_advances_live_slots_and_leaves_the_others_bit_for_bit(
        request, path, live):
    """One layer of the whole state array, in place, at the published head
    and state sizes; an empty slot's program moves nothing, whichever slots
    are live (the kernel's blocks lean on the nearest live slot's: first,
    last, none)."""
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    B, H, P, G, N = 4, 16, 64, 1, 128
    x, dt, A, Bm, Cm, s0 = _operands(B, 1, H, P, G, N, seed=7)
    state = jnp.stack([s0 * 0.5, s0, s0 * 2.0])
    run = jax.jit(lambda st, *a: ssd.ssd_step(st, 1, *a,
                                              force_xla=path == "xla"))
    args = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], jnp.asarray(live))
    assert ("pallas_call" in str(jax.make_jaxpr(run)(state, *args))) \
        == (path == "pallas")
    y, new = run(state, *args)
    want_y, want_s = _plain(x, dt, A, Bm, Cm, s0)
    on = np.asarray(live)
    tol = OP_TOL * np.abs(want_y).max()
    assert np.abs(np.asarray(y)[on] - want_y[on, 0]).max(initial=0) < tol
    assert np.abs(np.asarray(new[1])[on] - want_s[on]).max(initial=0) < tol
    assert (np.asarray(new[1])[~on] == np.asarray(state[1])[~on]).all()
    assert (np.asarray(new[0]) == np.asarray(state[0])).all()
    assert (np.asarray(new[2]) == np.asarray(state[2])).all()


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_step_gives_each_group_of_heads_its_own_b_and_c(request, path):
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    x, dt, A, Bm, Cm, s0 = _operands(2, 1, 4, 64, 2, 16, seed=9)
    y, new = ssd.ssd_step(s0[None], 0, x[:, 0], dt[:, 0], A, Bm[:, 0],
                          Cm[:, 0], jnp.array([True, True]),
                          force_xla=path == "xla")
    want_y, want_s = _plain(x, dt, A, Bm, Cm, s0)
    tol = OP_TOL * np.abs(want_y).max()
    assert np.abs(np.asarray(y) - want_y[:, 0]).max() < tol
    assert np.abs(np.asarray(new[0]) - want_s).max() < tol


# -- the mixer over the modes ------------------------------------------------


@pytest.mark.parametrize("path", ["seq", "seq_then_decode", "chunks"])
def test_the_state_space_mixer_equals_the_whole_sequence(model, path):
    """One sequence of 23 positions through the mixer: whole (`Seq`); 9
    positions kept (`Seq` with `keep`, padded to 16) and then 14 `Decode`
    steps from the tail and the state matrix; three chunks of 8 from
    carried state, the last with 7 real positions. Row 4 of 7; D drawn, not
    the recipe's ones, so that the skip term is a head's own."""
    spec, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[1], params["layers"][0][0])
    lp = {**lp, "s_D": jax.random.normal(jax.random.PRNGKey(6), (8,))}
    plain = {n: w for n, w in lp.items() if n.startswith("s_")}
    T, si = 23, 4
    u = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.mamba2(u[0], plain, spec)
    state = stack.new_request_state(cfg, 1, jnp.float32)
    tables = jnp.ones((1, 4), jnp.int32)  # page 1: a live slot
    if path == "seq":
        got, _ = stack._ssd(u, lp, cfg, si, stack.Seq(cfg), {})
    elif path == "seq_then_decode":
        n, pad = 9, 16
        head = jnp.zeros((1, pad, cfg.d_model)).at[:, :n].set(u[:, :n])
        mode = stack.Seq(cfg, n_valid=jnp.array([n]), keep=True)
        first, carry = stack._ssd(head, lp, cfg, si, mode, dict(state))
        outs = [first[:, :n]]
        for t in range(n, T):
            mode = stack.Decode(cfg, jnp.array([t]), tables, PAGE)
            o, carry = stack._ssd(u[:, t:t + 1], lp, cfg, si, mode, carry)
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
            o, carry = stack._ssd(chunk, lp, cfg, si, mode, carry)
            outs.append(o[:, :n])
        got = jnp.concatenate(outs, axis=1)
        # the other layers' tails and state were left alone
        assert not np.asarray(carry["conv"][:si]).any()
        assert not np.asarray(carry["ssd"][:si]).any()
        assert np.asarray(carry["ssd"][si]).any()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=OP_TOL * float(jnp.abs(want).max()),
                               rtol=0)


# -- the whole model ---------------------------------------------------------


def test_forward_agrees_with_the_plain_reference(model):
    spec, family, cfg, params = model
    tokens = np.asarray(prompts(1, [family.PAD_TO])[0], np.int32)
    got, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens[None])
    at = np.arange(len(tokens))
    want = family.logits_at(params, jnp.asarray(tokens), jnp.asarray(at), spec)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("path,length", [
    ("bucket", 5),     # one bucket
    ("bucket", 16),    # a whole bucket
    ("chunked", 37),   # two chunk edges, the last chunk 5 tokens and padding
    ("chunked", 48),   # three whole chunks
])
def test_prefill_and_decode_agree_with_the_plain_reference(model, path, length):
    """Both prefill paths, then 30 decoded tokens through pages (the
    attention layer of the 8), convolution tails and state matrices,
    against the reference's one cache-less pass, on log-probabilities."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    try:
        assert (length > eng.ecfg.prefill_chunk) == (path == "chunked")
        assert eng.k_pages.shape == (1, 1, 96, PAGE, 2 * 8)
        assert eng.state["conv"].shape == (7, 2, 3, 192)
        assert eng.state["ssd"].shape == (7, 2, 16, 8 * 16)
        assert eng.state["ssd"].dtype == jnp.float32
        assert eng.stats()["state_bytes"] == 4 * (7 * 2 * 3 * 192
                                                  + 7 * 2 * 16 * 128)
        assert eng.prefix is None  # off by derivation: state beside pages
        prompt = prompts(1, [length], seed=length)[0]
        out = eng.generate(prompt, max_tokens=30)
    finally:
        eng.stop()
    want = log_softmax(reference_logits(model, prompt, out["token_ids"]))
    served = np.asarray(out["logprobs"])
    picked = want[np.arange(30), out["token_ids"]]
    assert np.abs(served - picked).max() < LOGPROB_TOL
    # greedy: the served token is the reference's best (or within rounding)
    assert (want.max(-1) - picked).max() < LOGPROB_TOL


def test_a_reused_slot_holds_nothing_of_its_last_occupant(model):
    """Three requests on two slots: the third takes the slot of whichever
    finishes first, so its tails and state matrices must be its own
    (install overwrites them); every install and every decode dispatch adds
    to the counters that `recurrent_state_live_share` reads."""
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
        want = log_softmax(reference_logits(model, p, r.output))
        picked = want[np.arange(len(r.output)), r.output]
        assert np.abs(np.asarray(r.output_logprobs) - picked).max() < LOGPROB_TOL


def test_sequences_that_wait_for_a_slot_hold_a_bounded_state(model):
    """A prefilled sequence keeps its tails and state matrices until a slot
    takes them (75.5 MB at published widths), so admission parks a request
    while the waiting ones hold what they may (`engine._state_room`: half
    of what the device has left over a sequence's state; the CPU keeps no
    count of its memory, so here it is None and the test sets 1). ONE count
    under the allocator's lock, up on admission, down when a slot takes the
    state or the request ends before one does. Six requests on two slots:
    every one is served the reference's tokens, some after being parked,
    nothing is left parked and the count is back at 0; a request cancelled
    while its state waits gives its place back too."""
    _, _, cfg, params = model
    assert get_config(CONFIG).ssd_dims[0] * 128 * 4096 * 4 > 75e6
    before = common.counters()
    eng = engine_for(cfg, params)
    try:
        assert eng._state_room is None and eng._states_out == 0
        eng._state_room = 1
        ps = prompts(6, [5, 21, 9, 12, 30, 7], seed=6)
        reqs = [Request(request_id=f"w{i}", prompt=p, max_tokens=10)
                for i, p in enumerate(ps)]
        for r in reqs:
            eng.add_request(r)
        for r in reqs:
            assert r.done.wait(300) and r.error is None
        assert not eng._waiting and not eng._ready and eng._states_out == 0
        # both slots taken for long, one sequence waits with its state, a
        # second is parked; the waiting one is cancelled and the parked one
        # takes its place
        long = [Request(request_id=f"l{i}", prompt=p, max_tokens=60)
                for i, p in enumerate(prompts(2, [6, 7], seed=7))]
        for r in long:
            eng.add_request(r)
        deadline = time.time() + 300
        while len(eng._active()) < 2 and time.time() < deadline:
            time.sleep(0.01)  # both are in their slots
        held, parked = (Request(request_id=n, prompt=p, max_tokens=4)
                        for n, p in zip("hp", prompts(2, [8, 9], seed=8)))
        eng.add_request(held)
        while not eng._ready and time.time() < deadline:
            time.sleep(0.01)
        eng.add_request(parked)
        while not eng._waiting and time.time() < deadline:
            time.sleep(0.01)
        assert eng._states_out == 1 and eng._waiting == [parked]
        eng.cancel(held.request_id)
        for r in long + [held, parked]:
            assert r.done.wait(300) and r.error is None
        assert held.finish_reason == "cancelled" and len(parked.output) == 4
        assert not eng._waiting and eng._states_out == 0
    finally:
        eng.stop()
    assert common.counter_delta(before, common.counters(),
                                "serve_requests_deferred",
                                reason="no_state_room") > 0
    for r, p in zip(reqs, ps):
        want = log_softmax(reference_logits(model, p, r.output))
        picked = want[np.arange(len(r.output)), r.output]
        assert np.abs(np.asarray(r.output_logprobs) - picked).max() < LOGPROB_TOL
    # a model whose pages are its whole state is not bounded this way
    plain = get_config("tiny-llama")
    bare = InferenceEngine(init_params(plain, jax.random.PRNGKey(0)), plain,
                           EngineConfig(max_pages=16, max_batch_size=2))
    try:
        assert bare._state_room is None
    finally:
        bare.stop()


def test_an_empty_slots_state_is_untouched_by_a_decode_step(model):
    """The decode program over two slots of which one holds a sequence:
    the other's state matrices come back bit for bit (its table starts at
    the trash page), though its conv tail, like every slot's, shifts."""
    _, _, cfg, params = model
    key = jax.random.PRNGKey(8)
    state = stack.new_engine_state(cfg, 2, PAGE, jnp.float32, jnp.float32)
    state = {**state, "ssd": jax.random.normal(key, state["ssd"].shape)}
    pool = jnp.zeros(stack.pool_shape(1, 8, PAGE, 2, 8), jnp.float32)
    tables = jnp.array([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    mode = stack.Decode(cfg, jnp.array([5, 0]), tables, PAGE)
    _, _, _, new = jax.jit(lambda p, t, pools, st: stack.run_paged(
        p, t, cfg, mode, pools, st))(
            params, jnp.array([[7], [0]], jnp.int32), (pool, pool), state)
    old, got = np.asarray(state["ssd"]), np.asarray(new["ssd"])
    assert (got[:, 1] == old[:, 1]).all()
    assert (got[:, 0] != old[:, 0]).any()


def test_what_assumes_pages_are_the_whole_state_is_refused(model):
    _, _, cfg, params = model
    with pytest.raises(ValueError, match="recurrent state"):
        engine_for(cfg, params, speculation={"mode": "ngram",
                                             "num_speculative_tokens": 2})
    with pytest.raises(ValueError, match="no sharding rules"):
        InferenceEngine(params, cfg, EngineConfig(max_pages=8), mesh=object())
    eng = engine_for(cfg, params)
    try:
        with pytest.raises(ValueError, match="keeps state beside its pages"):
            eng._refuse_kv_transfer("export_kv_pages")
    finally:
        eng.stop()


# -- what a lower precision or a dropped scalar does -------------------------


@pytest.mark.parametrize("scalar,default", [
    ("embedding_multiplier", 1.0), ("attention_multiplier", None),
    ("residual_multiplier", 1.0), ("logits_scaling", 1.0)])
def test_each_scalar_moves_the_logits_when_it_is_dropped(model, scalar, default):
    """The program with one of the four scalars at the value that emits
    nothing (`attention_multiplier` None: head_dim ** -0.5 = 0.35 in place
    of 1 / 64) is far from the reference, so none can be lost unnoticed;
    with all four it is the reference (the test above)."""
    spec, family, cfg, params = model
    tokens = np.asarray(prompts(1, [64], seed=11)[0], np.int32)
    want = np.asarray(family.logits_at(
        params, jnp.asarray(np.pad(tokens, (0, family.PAD_TO - 64))),
        jnp.arange(64), spec))
    dropped = dataclasses.replace(cfg, **{scalar: default})
    got, _ = forward(params, tokens[None], dropped)
    assert np.abs(np.asarray(got[0]) - want).max() > 1000 * LOGIT_TOL


@pytest.mark.parametrize("mode", ["state-bf16", "int8", "fp8"])
def test_a_lower_precision_than_stated_fails(model, mode):
    """The control separates: the reference with a bfloat16 state, or with
    int8 or fp8 weights, in the program's place differs from itself by far
    more than the rounding of a sound run; and int8 and fp8 are two
    controls, not one."""
    prompt = prompts(1, [40], seed=9)[0]
    output = prompts(1, [24], seed=10)[0]
    exact = reference_logits(model, prompt, output)
    low = reference_logits(model, prompt, output, mode=mode)
    assert np.abs(low - exact).max() > 10 * LOGIT_TOL
    if mode == "int8":
        other = reference_logits(model, prompt, output, mode="fp8")
        assert np.abs(low - other).max() > 1000 * LOGIT_TOL


# -- the benchmark's side ----------------------------------------------------


def test_the_family_counts_a_steps_and_a_chunks_work_from_the_equations():
    spec = common.load_json("configs", CONFIG + ".json")
    family = common.family(spec)
    state = 64 * 64 * 128 * 4                      # 2 MiB a slot and layer
    step = family.work["ssd_step"](spec, 48)
    assert step["bytes"] == 48 * (2 * state + (2 * 4096 + 64 + 256) * 4)
    assert step["flops"] == 48 * 5 * 64 * 64 * 128
    chunk = family.work["ssd_chunk"](spec, 256)
    assert chunk["bytes"] == 256 * (2 * 4096 + 64 + 256) * 4 + 2 * state
    assert chunk["flops"] == 256 * (257 * (128 + 4096) + 4 * 128 * 4096)
    assert [family.calls_per_pass(spec, g) for g in
            ("ssd_step", "ssd_chunk", "paged_decode")] == [36, 36, 4]
    assert family.modes == ("int8", "fp8", "state-bf16")
    assert not hasattr(family, "nll_and_norm_grads")  # it only serves


def test_the_cell_is_listed_where_its_readers_read():
    from tests.test_benchmark_families import (LATER_CELLS, SETUP_READERS,
                                               manifest_without)

    # the manifest as this cell's PR left it: later PRs append theirs
    manifest = manifest_without(LATER_CELLS[LATER_CELLS.index(CELL) + 1:])
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "serve-chat-burst", 1)
    assert manifest["workloads"][-1] == entry
    assert manifest["configs"][-1]["name"] == CONFIG
    # its three ended the list; the parts of `setup_s` (PR 50) follow them
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("ssd_step_roofline")
    assert names[at:] == [
        "ssd_step_roofline", "ssd_step_device_share", "ssd_chunk_roofline",
        *SETUP_READERS, "setup_trainer_start_s"]
    assert all(m["workloads"] == [CELL]
               for m in manifest["per_layer"][at:at + 3])
    listing = {m["name"] for m in manifest["per_layer"]
               if CELL in m.get("workloads", ())}
    assert {"recurrent_state_live_share", "paged_decode_roofline",
            "pool_copy_device_share", "decode_live_slots.traced",
            "prefill_device_ms_per_ktok", "tpot_host_ms"} <= listing
    # every metric the cell is listed in moves what the cell reports, and
    # the cell ends the list it was appended to
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == ("setup_s" if m["name"] in SETUP_READERS
                                  else "tpot_mean_ms")
            assert m["workloads"][-1] == CELL
    cell = common.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_mean_ms", "setup_s"]
    assert cell["engine"] == {"max_seq_len": 2048, "max_batch_size": 64,
                              "max_pages": 8193}
    mix = cell["traffic"]
    assert mix["arrivals"] == {"process": "gamma", "cv": 1.5}
    assert "classes" not in mix and not mix.get("shared_prefix")
    assert (mix["prompt_len"]["median"], mix["output_len"]["median"],
            mix["schedule_seed"]) == (128, 256, 45)
    from benchmark import trace_reduce

    assert {"ssd_chunk", "ssd_step"} <= set(
        trace_reduce.load_names()["groups"])


def test_the_cpu_rehearsal_runs_the_new_cell_with_its_gamma_arrivals():
    """`granite-4.0-h-micro.serve-chat-burst` end to end at the family's
    tiny cut: the benchmark's own drivers, generator, warm-up, window,
    replay and comparison with the plain reference, the mix's bursty
    arrivals as they are and its lengths shrunk to the tiny engine."""
    import ray_tpu
    from benchmark import drive
    from benchmark.tests.tiny import tiny_cell

    cell = tiny_cell(CELL)
    assert cell["traffic"]["arrivals"] == {"process": "gamma", "cv": 1.5}
    # the tiny cut runs in the configuration's bfloat16, as the cell does
    cell["check"].update(sample=4, max_tokens=8, limits={
        "logprob_rms_err": 0.02, "greedy_gap_rms": 0.02})
    args = argparse.Namespace(seed=2**31 + 45, seconds=2.0, trace=0, sweep="")
    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    try:
        out = drive.measure(cell, args, {"platform": "cpu"},
                            common.CompileWatch(), time.perf_counter())
    finally:
        ray_tpu.shutdown()
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 6
    assert {"tpot_mean_ms", "setup_s"} <= set(out["end_to_end"])
    share = common.load_reader("recurrent_state_live_share")(
        {"counters": out["counters"]})
    assert 0 < share <= 100
    installed = common.counter_delta(*out["counters"],
                                     "serve_state_slots_installed")
    assert installed >= out["attempted"]
    # a trace without the kernels gives the new readers nothing to read
    empty = {"trace": {"ops": {}, "modules": {}, "module_ops": {},
                       "busy_s": 1.0}, "run": {}}
    for name in ("ssd_step_roofline", "ssd_step_device_share",
                 "ssd_chunk_roofline"):
        assert common.load_reader(name)(empty) is None
