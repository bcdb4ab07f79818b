"""The Kanana-2 stack (`mla`: latent attention as one mixer a layer, queries
projected directly, a leading dense layer and then every routed expert held
beside shared experts) against the plain reference of its family
(benchmark/reference/kanana.py: float32, `highest`, no kernel, no cache, no
absorbed form, nothing imported from the program), on seeded weights.

Tolerances. Weights are the family's bfloat16 draws cast to float32 and the
tiny model runs in float32, so program and reference differ only in the
order of float32 sums (the absorbed form against the plain one, one-pass
against blockwise softmax, a combine matrix against a scan over experts, the
shared product added after the routed sum's rounding point): logits agree to
LOGIT_TOL and log-probabilities to LOGPROB_TOL. The control rounds the same
weights to fp8 and must land far outside them."""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import kanana as ref
from benchmark.tests.tiny import tiny_cell, tiny_spec
from ray_tpu.models import StackConfig, get_config, stack
from ray_tpu.models.transformer import (
    _moe_ffn,
    _moe_ffn_dropless_ids,
    _moe_gate,
    _norm,
    _shared_experts,
    moe_ffn_groups,
    moe_ffn_step,
    moe_rows_computed,
    moe_seq_groups,
    moe_step_visits,
)
from ray_tpu.ops import mla_attention as mla
from ray_tpu.ops.moe import groups_fit
from ray_tpu.parallel.moe import sigmoid_bias_gating
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

CONFIG = "kanana-2-30b-a3b"
CELL = "kanana-2-30b-a3b.serve-agent"
# float32 on both sides: over 10x the largest differences seen over the
# cases below (logits 3e-6, log-probabilities 1.5e-6); the fp8 control reads
# 1e-3 rms and more
LOGIT_TOL = 5e-5
LOGPROB_TOL = 2e-5
PAGE = 4


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec(CONFIG)
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(48)))
    # at 64 wide the router's logits spread 0.16 and its scores are nearly
    # flat; 8 x the router gives the choice and the weights something to do
    params["layers"] = [tuple({n: w * (8.0 if n == "router" else 1.0)
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


@pytest.fixture(scope="module")
def engine(model):
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    yield eng
    eng.stop()


def reference_logits(model, prompt, output, mode=None):
    """One cache-less pass over prompt + output: the logits each output
    token was drawn from, float64 [len(output), vocab]."""
    spec, family, _, params = model
    seq = list(prompt) + list(output)
    padded = np.zeros((-(-len(seq) // family.PAD_TO) * family.PAD_TO,), np.int32)
    padded[:len(seq)] = seq
    at = len(prompt) - 1 + np.arange(len(output))
    return np.asarray(family.logits_at(params, jnp.asarray(padded),
                                       jnp.asarray(at), spec, mode), np.float64)


def reference_logprobs(model, prompt, output, mode=None):
    logits = reference_logits(model, prompt, output, mode)
    top = logits.max(-1, keepdims=True)
    lse = np.log(np.exp(logits - top).sum(-1, keepdims=True)) + top
    return (logits - lse)[np.arange(len(output)), output]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, n).tolist()


def expert_layer(model):
    """(cfg, the first expert layer's leaves, unstacked)."""
    _, _, cfg, params = model
    return cfg, jax.tree.map(lambda a: a[0], params["layers"][1][0])


# -- the stack's shape -------------------------------------------------------


def test_a_dense_layer_then_one_scan_over_one_pool_of_latent_rows(model):
    _, _, cfg, params = model
    assert cfg.layer_kinds == ("mla",) * 4
    assert cfg.second_halves == ("ffn", "moe", "moe", "moe")
    assert cfg.segments() == ((0, ("mla",), 1), (1, ("mla",), 3))
    # one attention a layer, ONE row a token: 16 + 8 lanes in a 128-lane tile
    assert cfg.cache_dims == (4, 1, 128) and cfg.latent_cache
    assert not cfg.has_state and not cfg.counts_choices
    dense, moe = params["layers"][0][0], params["layers"][1][0]
    assert dense["wq"].shape == (1, 64, 4, 24) and "wq_a" not in dense
    assert dense["w_in"].shape == (1, 64, 128) and "router" not in dense
    assert moe["wk_b"].shape == moe["wv_b"].shape == (3, 16, 4, 16)
    assert moe["wkv_a"].shape == (3, 64, 16) and moe["wkr"].shape == (3, 64, 8)
    assert moe["router"].shape == (3, 64, 8) and moe["w_in"].shape == (3, 8, 64, 32)
    assert moe["sh_in"].shape == moe["sh_gate"].shape == (3, 64, 64)
    assert moe["sh_out"].shape == (3, 64, 64)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.param_count()


def test_the_registered_model_counts_the_issues_parameters():
    big = get_config(CONFIG)
    assert round(big.param_count() / 1e9, 2) == 30.67
    assert big.segments() == ((0, ("mla",), 1), (1, ("mla",), 47))
    cut = dataclasses.replace(big, n_layers=8, layer_kinds=("mla",) * 8)
    # 64.10 + 7 x 640.03 + 525.34 M
    assert round(cut.param_count() / 1e6, 1) == 5069.6
    assert cut.segments() == ((0, ("mla",), 1), (1, ("mla",), 7))
    assert cut.cache_dims == (8, 1, 640)
    # the ROUTED experts' rows alone, whatever stands beside them
    assert moe_rows_computed(cut, 64, 1) == 128 * 64
    tiny = get_config("tiny-kanana")
    assert tiny.d_ff_shared == 64 and tiny.n_dense_layers == 1


def test_no_silent_fallback_at_the_published_shape(monkeypatch):
    """The three gates answer yes: the latent kernels take 32 heads over the
    640-lane row, a step visits, and a chunk of 256 and of 512 rows fits the
    grouped kernel's fast memory with 128 experts of 768."""
    spec = common.load_json("configs", CONFIG + ".json")
    cfg = common.family(spec).model_config(spec)
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")  # the gate's own answer
    q = jax.ShapeDtypeStruct((64, cfg.n_heads, cfg.latent_row), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((8, 1, 9, 16, cfg.latent_row), jnp.bfloat16)
    assert mla.latent_ok(q, pool, cfg.kv_lora_rank)
    assert moe_step_visits(cfg, None)
    assert moe_seq_groups(cfg, 1, 256, None) and moe_seq_groups(cfg, 1, 512, None)
    assert groups_fit(512, 2048, 128, 768, 2)


def test_what_the_kinds_refuse():
    base = dict(name="x", vocab_size=8, d_model=16, n_layers=2, n_heads=4,
                d_ff=16, kv_lora_rank=8, qk_nope_dim=4, qk_rope_dim=4,
                v_head_dim=4)
    with pytest.raises(ValueError, match="two shapes of pool rows"):
        StackConfig(**base, layer_kinds=("mla", "attn"))
    with pytest.raises(ValueError, match="two shapes of pool rows"):
        StackConfig(**base, layer_kinds=("mla", "mla2"), q_lora_rank=8,
                    num_experts=2)
    with pytest.raises(ValueError, match="an even `qk_rope_dim`"):
        StackConfig(**{**base, "qk_rope_dim": 3}, layer_kinds=("mla", "mla"))
    with pytest.raises(ValueError, match="mla2 layers need `q_lora_rank`"):
        StackConfig(**base, layer_kinds=("mla2", "mla2"), num_experts=2)
    with pytest.raises(ValueError, match="no shared experts"):
        StackConfig(**base, layer_kinds=("mla2", "mla2"), q_lora_rank=8,
                    num_experts=2, d_ff_shared=8)
    with pytest.raises(ValueError, match="stand beside routed ones"):
        StackConfig(**base, layer_kinds=("mla", "mla"), d_ff_shared=8)
    ok = StackConfig(**base, layer_kinds=("mla", "mla"), num_experts=2,
                     n_dense_layers=1, d_ff_shared=8)
    assert ok.second_halves == ("ffn", "moe") and ok.cache_dims == (2, 1, 128)


# -- served logits against the reference -------------------------------------


def test_the_forward_pass_gives_the_references_logits(model):
    spec, family, cfg, params = model
    tokens = jnp.asarray(prompt_of(ref.Q_BLOCK, 1))
    got, _ = stack.forward(params, tokens[None], cfg)
    want = family.logits_at(params, tokens, jnp.arange(len(tokens)), spec)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() < LOGIT_TOL
    control = family.logits_at(params, tokens, jnp.arange(len(tokens)), spec,
                               "fp8")
    assert np.abs(np.asarray(got[0]) - np.asarray(control)).max() \
        > 20 * LOGIT_TOL


@pytest.mark.parametrize("n_prompt", [5, 21, 40],
                         ids=["bucket", "chunked", "chunks_3"])
def test_served_logprobs_are_the_references(model, engine, n_prompt):
    """Bucket prefill (the plain form), chunked prefill and decode through
    the latent pool (the absorbed form), against one cache-less pass."""
    prompt = prompt_of(n_prompt, n_prompt)
    got = engine.generate(prompt, max_tokens=7)
    want = reference_logprobs(model, prompt, got["token_ids"])
    assert np.abs(np.asarray(got["logprobs"]) - want).max() < LOGPROB_TOL
    control = reference_logprobs(model, prompt, got["token_ids"], "fp8")
    assert np.sqrt(np.mean((np.asarray(got["logprobs"]) - control) ** 2)) \
        > 20 * LOGPROB_TOL
    assert engine.v_pages is None and engine.state == {}
    assert engine.k_pages.shape[0] == 4 and engine.k_pages.shape[-1] == 128


def test_a_later_turn_is_served_from_the_prefix_cache(model, engine):
    """A session's second turn: the first turn's prompt, a stand-in answer
    and new tokens. The first turn's whole pages are hits, all nine of them
    (36 tokens: two chunks and a page), and the logits are the cache-less
    reference's."""
    _, _, cfg, params = model
    first = prompt_of(36, 7)
    engine.generate(first, max_tokens=5)
    second = first + prompt_of(6, 8) + prompt_of(9, 9)
    before = common.counters()
    again = engine.generate(second, max_tokens=5)
    assert common.counter_delta(before, common.counters(),
                                "serve_prefix_cache_hit_tokens") == 36
    want = reference_logprobs(model, second, again["token_ids"])
    assert np.abs(np.asarray(again["logprobs"]) - want).max() < LOGPROB_TOL
    cold = engine_for(cfg, params, prefix_caching=False)
    try:
        plain = cold.generate(second, max_tokens=5)
    finally:
        cold.stop()
    assert again["token_ids"] == plain["token_ids"]


# -- the layer's parts -------------------------------------------------------


def test_the_direct_query_and_the_dense_layer_are_the_references(model):
    """Layer 0 alone: one projection to the heads' queries, no bottleneck and
    no norm, then the dense SwiGLU."""
    spec, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][0][0])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, ref.Q_BLOCK, 64)) * 0.5
    mode = stack.Seq(cfg)
    mode.at = None
    got, _ = jax.jit(lambda x, lp: stack._layer(
        x, lp, cfg, "mla", "ffn", 0, 0, mode, {}))(x, lp)
    want = ref.layer(x[0], lp, spec, dense=True)
    assert np.abs(got[0] - want).max() < 2e-6
    # the attention alone, so that the second half cannot hide it
    h = _norm(x, lp["ln1"], None, cfg)
    o, _ = stack._mla(h, lp, cfg, 0, mode, {})
    with jax.default_matmul_precision("highest"):
        attn = ref.mla(h[0], lp, spec)
    assert np.abs(o[0] - attn).max() < 2e-6
    # a bottleneck's leaves are another model's: none here, and asking for
    # one changes the tree
    assert "wq" in stack.layer_shapes(cfg, "mla") and "wq_a" in stack.layer_shapes(
        dataclasses.replace(cfg, q_lora_rank=8), "mla")


def _forms(cfg, lp, b):
    """The expert half over b [B,T,D] in each form `_experts` takes, the
    shared product added as `_ffn_half` adds it."""
    stacks = {n: lp[n][None] for n in ("w_in", "w_gate", "w_out")}
    lifted = {**{n: w for n, w in lp.items() if n not in stacks},
              "experts": (stacks, 0)}
    B, T, _ = b.shape
    shared = _shared_experts(b, lp, cfg)
    # a capacity of 4 slots an expert and row: the gather form, which drops
    # nothing where a row holds one token
    under = dataclasses.replace(cfg, capacity_factor=1.0)
    out = {"dropless": _moe_ffn_dropless_ids(b, lp, cfg)[0],
           "groups": moe_ffn_groups(b, lifted, cfg, None,
                                    jnp.ones((B, T), bool))[0],
           "capacity": _moe_ffn(b, lp, under)[0]}
    if T == 1:
        out["step"] = moe_ffn_step(b, lifted, cfg, None,
                                   jnp.ones((B,), bool))[0]
    return {name: y + shared for name, y in out.items()}, shared


@pytest.mark.parametrize("shape", [(6, 1), (1, 24)], ids=["step", "chunk"])
def test_the_shared_experts_stand_beside_every_form_of_the_routed(model, shape):
    """Step, groups, dropless and (one token a row, where its capacity drops
    nothing) the gather form, each plus the shared product, against the
    reference's ONE product of width n x w beside its scan over the
    experts."""
    spec = model[0]
    cfg, lp = expert_layer(model)
    b = jax.random.normal(jax.random.PRNGKey(5), (*shape, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(b.reshape(-1, 64), lp, spec)
    forms, shared = _forms(cfg, lp, b)
    step = shape[1] == 1
    assert set(forms) == {"dropless", "groups", "capacity"} | (
        {"step"} if step else set())
    for name, got in forms.items():
        if name == "capacity" and not step:
            continue  # 24 tokens a row overflow 4 slots: it may drop
        assert np.abs(got.reshape(-1, 64) - want).max() < 2e-6, name
    # the shared product is a real part of the sum, and the routed one too
    assert np.abs(shared).max() > 1e-4
    assert np.abs(forms["dropless"] - shared).max() > 1e-4


def test_the_shared_pair_is_the_sum_of_two_experts_of_half_the_width(model):
    """Form (b) of the issue, as arithmetic: the shared SwiGLU of width 2 w
    IS two experts of width w that every row visits with weight 1 (`sh_in`,
    `sh_gate` split by columns and `sh_out` by rows)."""
    cfg, lp = expert_layer(model)
    b = jax.random.normal(jax.random.PRNGKey(6), (1, 12, 64))
    one = _shared_experts(b, lp, cfg)
    w = cfg.d_ff_shared // 2
    two = sum(_shared_experts(b, {"sh_in": lp["sh_in"][:, s],
                                  "sh_gate": lp["sh_gate"][:, s],
                                  "sh_out": lp["sh_out"][s]}, cfg)
              for s in (slice(0, w), slice(w, None)))
    assert np.abs(one - two).max() < 2e-6


def test_the_router_is_the_published_rule_and_the_bias_moves_the_choice_alone(
        model):
    spec = model[0]
    cfg, lp = expert_layer(model)
    b = jax.random.normal(jax.random.PRNGKey(8), (1, 64, 64))
    lp = dict(lp, router_bias=jnp.zeros(8).at[7].set(0.3))
    _, w, ids = _moe_gate(b, lp, cfg)
    want_w, want_ids = ref.route(b[0], lp, spec)
    assert np.array_equal(np.sort(ids[0], -1), np.sort(want_ids, -1))
    order = np.argsort(ids[0], -1), np.argsort(want_ids, -1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w[0]), order[0], -1),
                               np.take_along_axis(np.asarray(want_w), order[1], -1),
                               rtol=2e-6)  # 1e-6 against 1e-20 in the sum
    # renormalised and scaled: the three weights sum to 2.448
    np.testing.assert_allclose(w.sum(-1), 2.448, rtol=1e-5)
    # the bias changes choices, and is in no weight
    _, _, plain = _moe_gate(b, dict(lp, router_bias=jnp.zeros(8)), cfg)
    moved = int(jnp.sum(jnp.any(ids == 7, -1))) - int(jnp.sum(jnp.any(plain == 7, -1)))
    assert moved > 0
    score = jax.nn.sigmoid(b[0] @ lp["router"])
    picked = jnp.take_along_axis(score, ids[0], -1)
    np.testing.assert_allclose(
        w[0], 2.448 * picked / picked.sum(-1, keepdims=True), rtol=2e-6)
    w0, _ = sigmoid_bias_gating(b[0] @ lp["router"], lp["router_bias"], 3,
                                True, 2.448)
    np.testing.assert_allclose(w0, w[0], rtol=1e-6)


# -- counters ----------------------------------------------------------------


def test_shared_rows_and_routed_rows_are_counted_apart(model, engine):
    """Every row of every dispatched program passes through the shared
    experts of each expert layer; the counters that speak of routed experts
    count the 8 routed ones alone."""
    before = common.counters()
    engine.generate(prompt_of(21, 3), max_tokens=6)
    delta = lambda name, **tags: common.counter_delta(  # noqa: E731
        before, common.counters(), name, **tags)
    layers, k = 3, 3
    # a prompt of 21 at chunk 16: one wide chunk of 32 rows (the model has
    # routed experts), then spans of 4 steps x 2 slots
    assert delta("serve_moe_shared_rows") >= layers * (32 + 2 * 4)
    assert delta("serve_moe_shared_rows") % layers == 0
    assert delta("serve_moe_rows_routed") >= layers * k * 21
    assert delta("serve_moe_rows_routed") % (layers * k) == 0
    held = delta("serve_moe_expert_steps", state="held")
    assert held > 0 and held % (layers * 8) == 0  # 8 routed experts, not 10
    assert 0 < delta("serve_moe_expert_steps", state="touched") <= held
    assert delta("serve_moe_choices") == 0  # every choice falls on a held one


def test_pages_taken_from_the_lru_are_counted(model):
    """A pool that fills: a second history pushes the first one's pages out
    of the prefix cache, the counter says how many, and `stats()` what
    the cache still holds unreferenced."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params, max_pages=25, max_seq_len=64)
    try:
        before = common.counters()
        first = prompt_of(40, 11)
        eng.generate(first, max_tokens=4)
        registered = common.counter_delta(
            before, common.counters(), "serve_prefix_cache_registered_pages")
        assert registered == 10  # whole pages of the prompt
        assert eng.stats()["reusable_pages"] == 10
        for seed in (12, 13):
            eng.generate(prompt_of(40, seed), max_tokens=4)
        evicted = common.counter_delta(
            before, common.counters(), "serve_prefix_cache_evicted_pages")
        assert evicted > 0
        again = eng.generate(first, max_tokens=4)
        want = reference_logprobs(model, first, again["token_ids"])
        assert np.abs(np.asarray(again["logprobs"]) - want).max() < LOGPROB_TOL
    finally:
        eng.stop()


# -- the benchmark's side ----------------------------------------------------


def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    import json

    spec = common.load_json("configs", CONFIG + ".json")
    manifest = common.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] == list(spec["reduced"])
    assert entry["source"] == spec["source"]
    assert 1 <= len(entry["why"]) <= 200  # the driver refuses a longer line
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == spec["source"])
    except OSError:
        pytest.skip("no catalog beside the guides here")
    differs = {k for k, v in row["config"].items() if spec.get(k, "-") != v}
    assert differs == {"num_hidden_layers"}
    assert spec["published"]["num_hidden_layers"] == row["config"][
        "num_hidden_layers"] == 48


def test_the_cell_is_an_entry_and_its_readers_list_it():
    """Entries are found by name: a later PR appends behind them."""
    manifest = common.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert 1 <= len(entry["why"]) <= 200
    cell = common.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_mean_ms", "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for new in ("shared_expert_device_share",
                "prefix_cache_evicted_page_share"):
        assert new in names and by_name[new]["workloads"][0] == CELL
    for wanted in ("mla_decode_roofline", "mla_prefill_roofline",
                   "mla_attn_device_share", "prefix_hit_token_share",
                   "moe_experts_touched_share", "moe_experts_skipped_share",
                   "moe_ffn_device_share.tpot", "moe_rows_padding_factor",
                   "prefill_device_ms_per_ktok", "decode_live_slots.traced"):
        assert wanted in names and CELL in by_name[wanted]["workloads"]
    assert {m["moves"] for m in cell["per_layer"]} == {"tpot_mean_ms", "setup_s"}
    mix = cell["traffic"]
    assert mix["sessions"]["turns"]["median"] == 5
    assert mix["shared_prefix"] == {"count": 4, "len": 6144, "zipf_s": 1.0}
    assert abs(cell["rate_rps"] - 0.8 * cell["knee_rps"]) < 1e-9


def test_the_schedules_longest_history_fits_the_engine():
    """`max_seq_len` is what the mix forces, in whole pages: the longest
    history of the schedule plus its answer."""
    from benchmark import traffic

    cell = common.load_cell(CELL)
    requests = traffic.requests(cell["traffic"], 1, cell["rate_rps"], 40.0,
                                cell["config"]["vocab_size"])
    longest = max(len(r["prompt_ids"]) + r["max_tokens"] for r in requests)
    assert longest <= cell["engine"]["max_seq_len"] <= 32768
    assert cell["engine"]["max_seq_len"] % 16 == 0
    assert cell["engine"]["max_seq_len"] - longest < 512
    sessions = round(cell["rate_rps"] * 40.0)  # the rate is of sessions
    assert sessions == 64 and len(requests) == 221  # most are later turns


def test_the_schedules_hit_tokens_through_the_prefix_cache_alone():
    """The cell's schedule (the same sizes and instants on every seed)
    through `PrefixCache` and nothing else: each request in the order it is
    due looks its prompt up, then registers its whole pages, as the engine
    does. A later turn finds its session's previous prompt to the page:
    `prefix_hit_token_share` 93.17 (92.10 while a hit was cut down to a
    multiple of `prefill_chunk`, which the ledger's PR 48 line read)."""
    from benchmark import traffic
    from ray_tpu.serve.engine import PrefixCache

    cell = common.load_cell(CELL)
    ecfg = EngineConfig(**cell["engine"])
    requests = traffic.requests(cell["traffic"], 49, cell["rate_rps"], 40.0,
                                cell["config"]["vocab_size"])
    cache, held = PrefixCache(ecfg.page_size), 0
    hit, tails = 0, []
    for r in requests:
        prompt = r["prompt_ids"]
        pages = cache.lookup_acquire(prompt, ecfg.prefill_chunk)
        hit += len(pages) * ecfg.page_size
        tails.append(len(prompt) - len(pages) * ecfg.page_size)
        own = len(prompt) // ecfg.page_size - len(pages)
        pages += range(held + 1, held + 1 + own)
        held += own
        cache.register(prompt, pages)
        assert cache.release_and_filter(pages) == []
    sent = sum(len(r["prompt_ids"]) for r in requests)
    assert (len(requests), sent, hit) == (221, 1566522, 1459488)
    assert round(100 * hit / sent, 2) == 93.17
    # four cold system prompts; every other request resumes at a page, and
    # its tail is the turn's new tokens and the page its history ended in
    assert sum(t >= 6144 for t in tails) == 4 and sum(tails) == sent - hit
    assert all(t % ecfg.page_size == len(r["prompt_ids"]) % ecfg.page_size
               for t, r in zip(tails, requests))
    # the chunk programs the tails take with nobody else in the queue: the
    # wide one while more than `prefill_chunk` tokens are left (203 + 119
    # calls; 250 + 107 under the old rule)
    C = ecfg.prefill_chunk
    wide = sum(max(0, -(-(t - C) // (2 * C))) for t in tails)
    narrow = sum(0 < (t - 1) % (2 * C) + 1 <= C for t in tails)
    assert (wide, narrow) == (203, 119)
    assert sum(t <= C for t in tails) == 83  # one narrow chunk and no more


def test_the_new_readers_read_their_counters_and_nothing_without_them():
    shared = common.load_reader("shared_expert_device_share")
    evicted = common.load_reader("prefix_cache_evicted_page_share")
    assert evicted({"counters": None}) is None
    assert evicted({"counters": ({}, {})}) is None  # a program without them
    before = {("serve_prefix_cache_registered_pages", ()): 10.0,
              ("serve_prefix_cache_evicted_pages", ()): 1.0}
    after = {("serve_prefix_cache_registered_pages", ()): 110.0,
             ("serve_prefix_cache_evicted_pages", ()): 26.0}
    assert evicted({"counters": (before, after)}) == 25.0
    trace = {"busy_s": 2.0, "ops": {
        "%fusion.1 = bf16[64,1536]{1,0} fusion(bf16[64,2048]{1,0} %a)": [0.2, 4],
        "%fusion.2 = bf16[64,2048]{1,0} fusion(bf16[64,1536]{1,0} %b)": [0.1, 4],
        "%fusion.3 = bf16[64,6144]{1,0} fusion(bf16[64,2048]{1,0} %c)": [0.5, 4]}}
    assert abs(shared({"trace": trace}) - 15.0) < 1e-9
    assert shared({"trace": {"busy_s": 2.0, "ops": {}}}) is None


def test_the_cpu_rehearsal_runs_the_new_cell_with_sessions():
    """`kanana-2-30b-a3b.serve-agent` end to end at the family's tiny cut:
    the benchmark's own drivers, generator, warm-up, window, replay and
    comparison with the plain reference; the shared system prompts and the
    sessions shrunk to the tiny engine (benchmark/tests/tiny.py shrinks a
    mix's prompts and answers and leaves `shared_prefix` and `sessions`
    alone: PERF.md section 7). Later turns are served from the cache."""
    import ray_tpu
    from benchmark import drive

    cell = tiny_cell(CELL)
    cell["traffic"]["shared_prefix"].update(count=2, len=32)
    cell["traffic"]["prompt_len"].update(median=10, min=4, max=20)
    cell["traffic"]["sessions"]["turns"].update(median=2, min=2, max=3)
    cell["traffic"]["sessions"]["turn_gap_s"].update(min=0.2, max=0.5)
    cell["rate_rps"] = 3.0
    args = argparse.Namespace(seed=2**31 + 48, seconds=1.5, trace=0, sweep="")
    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    try:
        out = drive.measure(cell, args, {"platform": "cpu"},
                            common.CompileWatch(), time.perf_counter())
    finally:
        ray_tpu.shutdown()
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 4
    assert {"tpot_mean_ms", "setup_s"} <= set(out["end_to_end"])
    delta = lambda name, **tags: common.counter_delta(  # noqa: E731
        *out["counters"], name, **tags)
    assert delta("serve_prefix_cache_hit_tokens") >= 32
    assert delta("serve_prefix_cache_registered_pages") > 0
    assert delta("serve_moe_shared_rows") > 0
    assert delta("serve_moe_rows_routed") > 0
