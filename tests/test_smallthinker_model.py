"""The window-and-full expert stack (SmallThinker-21BA3B: "attn" layers of
full attention that encode no position beside rotary "swa" layers over a
window, each layer caching its own keys in one of two page spaces; ReGLU
experts chosen from the layer's input, before the attention) against the
plain reference of its family (benchmark/reference/smallthinker.py: float32,
`highest`, no kernel, no cache, no ring, nothing imported from the program),
on seeded weights, at tiny widths: window 16, pages of 4, two periods, 8
experts top 3.

Tolerances. Weights are the family's bfloat16 draws cast to float32 and the
tiny model runs in float32, so program and reference differ only in the
order of float32 sums (one-pass against blockwise softmax, a combine matrix
against a scan over experts, pages against whole sequences):
log-probabilities agree to LOGPROB_TOL = 2e-5, over 10 x the largest
difference seen (1.2e-6). The readings the configuration rules out land far
outside it: the router after the norm 1e-3 or more, a SiLU gate 4e-3 or
more, a bfloat16 router 2e-4 or more (rms over the same positions)."""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.tests.tiny import tiny_spec
from ray_tpu.models import get_config, stack
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

from engine_programs import PINNED, digest

CONFIG = "smallthinker-21b-a3b"
CELL = CONFIG + ".serve-mixedlen"
LOGPROB_TOL = 2e-5
PAGE, WINDOW, CHUNK = 4, 16, 16
# the ring: the window's pages and those of the widest chunk the engine runs
# (the model has routed experts: serve/engine.py `_wide_chunk`)
RING = WINDOW // PAGE + 2 * CHUNK // PAGE


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec(CONFIG)
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(41)))
    # at 64 wide the router's logits spread 0.24; 8 x the router gives the
    # choice and the weights something to do
    params["layers"] = [tuple({n: w * (8.0 if n == "router" else 1.0)
                               for n, w in lp.items()} for lp in segment)
                        for segment in params["layers"]]
    cfg = family.model_config(spec, dtype="float32")
    return spec, family, cfg, params


def engine_for(cfg, params, **kw):
    ecfg = dict(max_batch_size=2, page_size=PAGE, max_pages=97, max_seq_len=96,
                max_window_pages=1 + 2 * RING, prefill_buckets=(8, 16),
                prefill_chunk=CHUNK, decode_span=4, busy_span=2,
                cache_dtype="float32")
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


def logprobs_of(logits, output):
    top = logits.max(-1, keepdims=True)
    lse = np.log(np.exp(logits - top).sum(-1, keepdims=True)) + top
    return (logits - lse)[np.arange(len(output)), output]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, n).tolist()


def request_of(n, seed=0, **kw):
    return Request(f"r{n}-{seed}", prompt_of(n, seed), **kw)


# -- (f) the configuration and the stack's shape -----------------------------


def catalog_row():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        return next(row for row in map(json.loads, f)
                    if row["name"] == "SmallThinker-21BA3B-Instruct")


def test_the_configuration_is_the_catalogs_row_outside_reduced():
    spec = common.load_json("configs", CONFIG + ".json")
    entry = next(c for c in common.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry) == ["file", "name", "reduced", "source", "why"]
    assert entry["reduced"] == sorted(spec["reduced"]) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    try:
        row = catalog_row()
    except OSError:
        pytest.skip("the catalog is not on this machine")
    assert entry["source"] == spec["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert spec["published"][key] == value
        else:
            assert spec[key] == value, key
    assert spec["num_hidden_layers"] == 12
    for layout in ("rope_layout", "sliding_window_layout"):
        assert spec[layout] == row["config"][layout][:12]
    assert {"router_input", "secondary_experts", "biases", "qk_norm",
            "rotary_layout", "sublayer_order", "expert_gate", "router_scores",
            "weights"} <= set(spec["assumed"])


def test_the_cut_and_the_whole_count_their_parameters():
    spec = common.load_json("configs", CONFIG + ".json")
    family = common.family(spec)
    cut = family.model_config(spec)
    assert round(cut.param_count() / 1e6) == 5561
    assert cut.segments() == ((0, ("attn", "swa", "swa", "swa"), 3),)
    whole = get_config(CONFIG)
    assert round(whole.param_count() / 1e9, 1) == 21.5
    assert dataclasses.replace(
        whole, name=cut.name, n_layers=12,
        layer_kinds=whole.layer_kinds[:12], router_aux_coef=0.0) == cut
    # two page spaces of one row shape: 4 heads of 128, 512 lanes
    assert cut.cache_dims == (3, 4, 128)
    assert cut.window_cache_dims == (9, 4, 128)
    assert cut.window_paged and cut.has_state and not cut.counts_choices


def test_what_is_still_two_shapes_is_still_refused():
    tiny = get_config("tiny-smallthinker")
    for kinds, what in ((("attn", "full"), "layers that cache keys"),
                        (("swa", "full"), "layers that cache keys"),
                        (("swa", "window"), "window layers' keys")):
        with pytest.raises(ValueError, match=f"two shapes of {what}"):
            dataclasses.replace(tiny, n_layers=2, layer_kinds=kinds)
    with pytest.raises(ValueError, match="window layers need"):
        dataclasses.replace(tiny, window=0)
    with pytest.raises(ValueError, match="unknown router_input"):
        dataclasses.replace(tiny, router_input="mixer")


def test_the_engine_holds_two_page_spaces(model, engine):
    _, _, cfg, _ = model
    assert engine.k_pages.shape == (2, 1, 97, PAGE, 16)
    assert engine.state["wk"].shape == (6, 1, 1 + 2 * RING, PAGE, 16)
    assert sorted(engine.state) == ["wk", "wv"]
    assert engine.abstract_pool().shape == engine.k_pages.shape
    assert {k: v.shape for k, v in engine.abstract_state().items()} == {
        k: v.shape for k, v in engine.state.items()}
    stats = engine.stats()
    assert stats["window_ring_pages"] == RING == engine._ring
    assert stats["free_window_pages"] == 2 * RING
    assert stats["free_pages"] == 96


# -- (a) (c) forward against the reference and its control modes --------------


def test_forward_is_the_reference(model):
    spec, family, cfg, params = model
    tokens = jnp.asarray([prompt_of(64, seed=1)])
    logits, _ = stack.forward(params, tokens, cfg)
    ref = family.logits_at(params, tokens[0], jnp.arange(64), spec)
    assert float(jnp.abs(logits[0] - ref).max()) < LOGPROB_TOL


@pytest.mark.parametrize("mode, least", [("router-after-norm", 1e-3),
                                         ("silu", 4e-3),
                                         ("router-bf16", 2e-4)])
def test_the_readings_the_configuration_rules_out_disagree(model, mode, least):
    """The program reads the router's input before the norm, gates by ReLU
    and scores in float32: the reference's other readings stand 10 x the
    tolerance and more away from it, so the tolerance tells them apart."""
    spec, family, cfg, params = model
    tokens = jnp.asarray([prompt_of(64, seed=2)])
    logits, _ = stack.forward(params, tokens, cfg)
    other = family.logits_at(params, tokens[0], jnp.arange(64), spec, mode)
    rms = float(jnp.sqrt(jnp.mean((logits[0] - other) ** 2)))
    assert rms > least >= 10 * LOGPROB_TOL


# -- (b) through the pages, past the window ----------------------------------


@pytest.mark.parametrize("n_prompt, n_out", [(5, 40), (16, 40), (21, 48),
                                             (50, 44)],
                         ids=["bucket", "bucket-of-a-window", "chunked",
                              "chunked-past-the-ring"])
def test_served_logprobs_are_the_references(model, engine, n_prompt, n_out):
    """Bucket prefill and chunked prefill, then decoding through the pages
    to 2.5 windows and more (the ring of 8 pages wraps twice and more): the
    served log-probabilities are the reference's full forward's."""
    prompt = prompt_of(n_prompt, seed=n_prompt)
    out = engine.generate(prompt, max_tokens=n_out)
    assert n_prompt + n_out >= 2.5 * WINDOW and len(out["token_ids"]) == n_out
    ref = logprobs_of(reference_logits(model, prompt, out["token_ids"]),
                      out["token_ids"])
    assert np.abs(np.asarray(out["logprobs"]) - ref).max() < LOGPROB_TOL


def test_a_silu_gate_fails_the_served_tolerance(model, engine):
    prompt = prompt_of(21, seed=7)
    out = engine.generate(prompt, max_tokens=24)
    err = {mode: np.abs(np.asarray(out["logprobs"]) - logprobs_of(
        reference_logits(model, prompt, out["token_ids"], mode),
        out["token_ids"])).max() for mode in (None, "silu", "router-after-norm")}
    assert err[None] < LOGPROB_TOL
    assert err["silu"] > 10 * LOGPROB_TOL
    assert err["router-after-norm"] > 10 * LOGPROB_TOL


# -- (d) the allocator -------------------------------------------------------


def test_a_sequence_takes_its_pages_as_it_grows_and_a_ring_at_the_most(model):
    """Admission promises a sequence the pages of prompt + max_tokens in
    both spaces and hands it none; as it grows to n tokens it holds ceil(n
    / ps) pages of the full space and min(ceil(n / ps), ring) of the window
    space, never more than the promise, and gives back what it holds and
    what it never took."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    free = lambda: (eng.stats()["free_pages"],  # noqa: E731
                    eng.stats()["free_window_pages"])
    try:
        pages = eng._admit_for_prefill(request_of(50, max_tokens=23))[0]
        total = -(-73 // PAGE)
        assert (len(pages), len(pages.window)) == (0, 0)
        assert (pages.promised, pages.window_promised) == (total, RING)
        # promised pages are nobody else's, taken or not
        assert free() == (96 - total, 2 * RING - RING)
        for n in (1, 4, 5, 16, 17, 31, 32, 33, 50, 72, 73, 90):
            eng._grow(pages, n)
            held = min(-(-n // PAGE), total)
            assert (len(pages), len(pages.window)) == (held, min(held, RING)), n
            assert free() == (96 - total, 2 * RING - RING)
        assert (pages.promised, pages.window_promised) == (0, 0)
        assert len(set(pages)) == total and len(set(pages.window)) == RING
        eng._free_pages_and_revive(pages)
        assert free() == (96, 2 * RING)
        # a sequence that ends early gives back what it never took
        pages = eng._admit_for_prefill(request_of(9, max_tokens=40))[0]
        eng._grow(pages, 9)
        assert (len(pages), len(pages.window)) == (3, 3)
        eng._free_pages_and_revive(pages)
        assert free() == (96, 2 * RING)
    finally:
        eng.stop()


def test_a_short_window_pool_parks_resumes_and_frees_everything(model):
    """Short and long requests through a window space that holds two rings
    beside a full space that holds everything: the long ones wait for the
    window space, by name, every request finishes with the reference's
    tokens, and every page of both spaces is free at the end."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params, max_batch_size=4, max_pages=4 * 24 + 1)
    before = common.counters()
    lens = [(40, 30), (6, 10), (44, 28), (9, 12), (36, 40), (50, 20), (5, 8)]
    reqs = [request_of(p, seed=i, max_tokens=m)
            for i, (p, m) in enumerate(lens)]
    try:
        for r in reqs:
            eng.add_request(r)
        for r in reqs:
            assert r.done.wait(120) and r.error is None, r.error
        assert [len(r.output) for r in reqs] == [m for _, m in lens]
        delta = lambda name, **tags: common.counter_delta(  # noqa: E731
            before, common.counters(), name, **tags)
        assert delta("serve_requests_deferred", reason="no_window_pages") > 0
        assert delta("serve_requests_deferred", reason="no_pages") == 0
        held = delta("serve_window_page_steps", state="held")
        assert 0 < held <= delta("serve_window_page_steps", state="bound")
        assert held < delta("serve_window_page_steps", state="full_length")
        assert 0 < delta("serve_moe_expert_steps", state="touched") \
            <= delta("serve_moe_expert_steps", state="held")
        deadline = time.monotonic() + 10  # unforeseen endings free a span late
        while time.monotonic() < deadline and eng.stats()["free_window_pages"] < 2 * RING:
            time.sleep(0.05)
        stats = eng.stats()
        assert (stats["free_pages"], stats["free_window_pages"]) == (96, 2 * RING)
        assert stats["waiting_for_pages"] == 0
    finally:
        eng.stop()
    r = reqs[4]  # a long one that waited: the pages it got hold its own keys
    ref = logprobs_of(reference_logits(model, r.prompt, r.output), r.output)
    assert np.abs(np.asarray(r.output_logprobs) - ref).max() < LOGPROB_TOL


# -- (e) refusals ------------------------------------------------------------


def test_refusals_name_the_stack_and_the_reason(model):
    _, _, cfg, params = model
    name = cfg.name
    with pytest.raises(ValueError, match=f"{name}.*page behind the window"):
        engine_for(cfg, params, speculation={"mode": "ngram",
                                             "num_speculative_tokens": 2})
    with pytest.raises(ValueError, match=f"{name}.*no sharding rules"):
        InferenceEngine(params, cfg, EngineConfig(max_pages=8), mesh=object())
    with pytest.raises(ValueError, match=f"{name}.*max_window_pages"):
        engine_for(cfg, params, max_window_pages=RING)
    with pytest.raises(ValueError, match=f"{name}.*longer than the window"):
        engine_for(cfg, params, prefill_buckets=(8, 32))
    eng = engine_for(cfg, params)
    try:
        # prefix caching is off by derivation: a page behind the window is
        # overwritten, so a content-addressed page would not stand for
        # every layer
        assert eng.ecfg.prefix_caching and eng.prefix is None
        with pytest.raises(ValueError, match=f"{name}.*second page space"):
            eng._refuse_kv_transfer("export_kv_pages")
        req = request_of(5, max_tokens=2, prefill_only=True)
        eng.add_request(req)
        assert req.done.wait(10) and "second page space" in req.error
    finally:
        eng.stop()
    mode = stack.Verify(cfg, jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1, 4), jnp.int32), PAGE,
                        jnp.zeros((1,), jnp.int32))
    with pytest.raises(NotImplementedError, match=f"{name}.*two page spaces"):
        mode.attend_paged_window({}, 0, None, None, None, 1.0)


# -- the six accepted configurations keep their programs ----------------------

# `_ffn_half`'s and the expert forms' new argument, `_qkv`'s, the activation's
# one place and the engine's second page space leave the decode, chunk and
# bucket programs of every family the benchmark held before this one the text
# they were (tests/engine_programs.py: PINNED, and whose text each is)
ACCEPTED = sorted((name, program) for name, program in PINNED
                  if name != "tiny-smallthinker" and program != "verify")


@pytest.mark.parametrize("name, program", ACCEPTED)
def test_the_accepted_families_programs_lower_to_the_parents(name, program):
    assert digest(name, program) == PINNED[name, program]


# -- (g) the cell, rehearsed -------------------------------------------------


def test_the_cell_is_listed_where_its_readers_read():
    manifest = common.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "serve-mixedlen", 1)
    listing = {m["name"] for m in manifest["per_layer"]
               if CELL in m.get("workloads", ())}
    assert {"paged_chunk_attn_roofline", "moe_experts_touched_share",
            "kv_pages_held_share.window", "hybrid_decode_attn_roofline",
            "window_pages_held_share", "moe_rows_padding_factor",
            "moe_ffn_device_share.tpot", "pool_copy_device_share",
            "prefill_device_ms_per_ktok"} <= listing
    assert "paged_decode_roofline" not in listing
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("paged_chunk_attn_roofline")  # later PRs appended theirs
    assert names[at:at + 3] == [
        "paged_chunk_attn_roofline", "moe_experts_touched_share",
        "kv_pages_held_share.window"]
    cell = common.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_mean_ms", "setup_s"]
    assert cell["engine"] == {"max_seq_len": 16384, "max_batch_size": 64,
                              "max_pages": 12289, "max_window_pages": 8193}


def test_the_family_counts_a_chunks_attention_from_its_start_and_tokens():
    spec = common.load_json("configs", CONFIG + ".json")
    family = common.family(spec)
    W = spec["sliding_window_size"]
    first = family.chunk_keys(spec, 0, 256, window=True)
    assert first == family.chunk_keys(spec, 0, 256, window=False)
    assert first == {"reads": 256, "pairs": 256 * 257 // 2}
    deep = family.chunk_keys(spec, 8192, 256, window=True)
    assert deep == {"reads": W + 255, "pairs": 256 * W}
    assert family.chunk_keys(spec, 8192, 256, window=False) == {
        "reads": 8448, "pairs": sum(range(8193, 8449))}
    work = family.chunk_attention_work(spec, 8192, 256)
    one = family.paged_chunk(spec, W + 255, 256 * W)
    assert work["bytes"] > 9 * one["bytes"] and work["flops"] > 9 * one["flops"]
    assert family.decode_attention_tokens(spec, 10000) == {
        "paged_decode_window": 9 * W, "paged_decode": 3 * 10000}


def test_the_cpu_rehearsal_runs_the_new_cell():
    """`smallthinker-21b-a3b.serve-mixedlen` end to end at the family's tiny
    cut: the benchmark's own drivers, generator, warm-up, window, replay and
    comparison with the plain reference, BOTH classes shrunk to the tiny
    engine and the long one still past the tiny window
    (benchmark/tests/tiny.py shrinks a mix's one `prompt_len` and knows no
    `classes`: PERF.md section 7). The new readers read the recorded
    counters."""
    import ray_tpu
    from benchmark import drive

    manifest = common.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    spec = tiny_spec(CONFIG)
    cell = common.load_cell(CELL)
    cell["config"] = spec
    cell["engine"] = dict(max_seq_len=96, max_batch_size=4, max_pages=97,
                          max_window_pages=1 + 3 * RING, page_size=PAGE,
                          prefill_buckets=(8, 16), prefill_chunk=CHUNK,
                          decode_span=4, busy_span=2, cache_dtype="float32")
    short, long_ = cell["traffic"]["classes"]
    short["prompt_len"].update(median=8, min=3, max=24)
    short["output_len"].update(median=10, min=4, max=16)
    long_["prompt_len"].update(median=40, min=34, max=60)  # past 2 windows
    long_["output_len"].update(median=12, min=6, max=20)
    cell.update(rate_rps=12.0, drain_cap_s=60)
    # the tiny cut runs in the configuration's bfloat16, as the cell does
    cell["check"].update(sample=4, max_tokens=8, limits={
        "logprob_rms_err": 0.02, "greedy_gap_rms": 0.02})
    assert entry["chips"] == 1
    args = argparse.Namespace(seed=2**31 + 41, seconds=1.0, trace=0, sweep="")
    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    try:
        out = drive.measure(cell, args, {"platform": "cpu"},
                            common.CompileWatch(), time.perf_counter())
    finally:
        ray_tpu.shutdown()
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 10
    assert {"tpot_mean_ms", "setup_s"} <= set(out["end_to_end"])
    ctx = {"counters": out["counters"]}
    held = common.load_reader("kv_pages_held_share.window")(ctx)
    assert 0 < held < 100  # the long class passed its ring
    assert 0 < common.load_reader("window_pages_held_share")(ctx) <= 100
    touched = common.load_reader("moe_experts_touched_share")(ctx)
    assert 3 / 8 * 100 <= touched <= 100  # a lone row touches 3 of 8
    assert common.load_reader("moe_rows_padding_factor")(ctx) > 8 / 3
    # a program without the counters gives its readers nothing to read
    empty = {"counters": ({}, {})}
    assert common.load_reader("kv_pages_held_share.window")(empty) is None
    assert common.load_reader("moe_experts_touched_share")(empty) is None
