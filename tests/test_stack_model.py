"""A stack of unlike layers (models/stack.py, `tiny-sambay`) on the serving
engine, against the plain reference of its family
(benchmark/reference/sambay.py: float32, `highest`, no kernel, no cache,
nothing imported from the program), on seeded weights.

Tolerances. Weights are the family's bfloat16 draws cast to float32 and the
tiny model runs in float32, so program and reference differ only in the
order of float32 sums (CPU matmuls at default precision against `highest`,
one-pass against blockwise softmax, a carried state against one scan):
log-probabilities agree to LOGPROB_TOL. The control rounds the same weights
to fp8 and must land far outside it."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.tests.tiny import tiny_spec
from ray_tpu.models import forward, get_config, init_params
from ray_tpu.models import stack
from ray_tpu.ops import pool_shape
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

# float32 on both sides: 2e-5 is 50x the largest difference seen over the
# cases below (3.8e-7, engine against reference); the fp8 control reads
# 1.2e-2 at the most and 5.3e-3 rms
LOGPROB_TOL = 2e-5
WINDOW, PAGE = 16, 4  # the tiny cut's window; pages of 4: a ring of 5
STATE_GAIN = 8.0


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec("phi-4-mini-flash")
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(28)))
    # At 64 wide the scan state hardly reaches the logits (2.5e-7: a test
    # of conv tails, scan state and slot reset would have no teeth); with
    # the Mamba input and B / C / dt projections 8x larger, dropping the
    # state path moves them by 0.15, as much as they are large.
    params["layers"] = [
        tuple({n: w * (STATE_GAIN if n in ("m_in", "m_x") else 1.0)
               for n, w in lp.items()} for lp in segment)
        for segment in params["layers"]]
    cfg = family.model_config(spec, dtype="float32")
    assert cfg.window == WINDOW
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


def test_forward_agrees_with_the_plain_reference(model):
    spec, family, cfg, params = model
    tokens = np.asarray(prompts(1, [family.PAD_TO])[0], np.int32)
    got, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens[None])
    at = np.arange(len(tokens))
    want = family.logits_at(params, jnp.asarray(tokens), jnp.asarray(at), spec)
    # logits, every position: past the window, the gmu and the cross layers
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=LOGPROB_TOL, rtol=0)


@pytest.mark.parametrize("path,length", [
    ("bucket", 5),     # one bucket, shorter than the window
    ("bucket", 16),    # a whole bucket: the window exactly full
    ("chunked", 21),   # two chunks, the last one padded, past the window
    ("chunked", 48),   # three whole chunks
])
def test_prefill_and_decode_agree_with_the_plain_reference(model, path, length):
    """Both prefill paths, then 30 decoded tokens: the decode crosses the
    window's edge (position 16), page boundaries (every 4) and laps the
    ring (5 pages = 20 positions)."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    try:
        assert (length > eng.ecfg.prefill_chunk) == (path == "chunked")
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


def test_two_requests_share_a_batch_and_a_slot_is_reused(model):
    """Three requests on two slots: two decode side by side, the third
    takes the slot of whichever finishes first, so its state must be the
    third's own (install overwrites conv tails, scan state and the ring)."""
    _, _, cfg, params = model
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
        installed = common.counters().get(
            ("serve_state_slots_installed", ()), 0)
    finally:
        eng.stop()
    assert installed >= 3
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


def test_window_layers_hold_a_bounded_ring(model):
    """Whatever the sequence's length a window layer holds window / page + 1
    pages a slot: the pool's shape says so, and the counter pair never
    reads held over bound."""
    _, _, cfg, params = model
    ring = WINDOW // PAGE + 1
    eng = engine_for(cfg, params)
    try:
        assert stack.ring_pages(cfg, PAGE) == ring
        assert eng.state["wk"].shape == pool_shape(
            cfg.count("window"), 1 + 2 * ring, PAGE, cfg.pool_heads,
            cfg.pool_dim)
        assert eng.k_pages.shape[0] == 1  # ONE full layer's pages
        before = common.counters()
        out = eng.generate(prompts(1, [30], seed=5)[0], max_tokens=50)
        after = common.counters()
        assert eng.stats()["window_ring_pages"] == ring
        assert eng.stats()["page_pool"] == "full-attention layers"
    finally:
        eng.stop()
    assert len(out["token_ids"]) == 50  # 80 positions through a 20-position ring
    held = common.counter_delta(before, after, "serve_window_page_steps",
                                state="held")
    bound = common.counter_delta(before, after, "serve_window_page_steps",
                                 state="bound")
    assert 0 < held <= bound
    full = common.counter_delta(before, after, "serve_kv_page_steps",
                                pool="full", state="reserved")
    window = common.counter_delta(before, after, "serve_kv_page_steps",
                                  pool="window", state="reserved")
    assert full > 0 and window > 0


# -- what assumes that pages are a request's whole state refuses -------------


def test_prefix_hits_are_off_by_derivation(model):
    _, _, cfg, params = model
    eng = engine_for(cfg, params, prefix_caching=True)
    try:
        assert eng.prefix is None
        assert eng.prefix_digest()["hashes"] == []
    finally:
        eng.stop()


REFUSALS = {
    "speculation": lambda cfg, p: engine_for(
        cfg, p, speculation={"mode": "ngram", "num_speculative_tokens": 2}),
    "mesh": lambda cfg, p: InferenceEngine(
        p, cfg, EngineConfig(page_size=PAGE), mesh=jax.sharding.Mesh(
            np.array(jax.devices()[:1]), ("tp",))),
    "param_axes": lambda cfg, p: __import__(
        "ray_tpu.models", fromlist=["param_axes"]).param_axes(cfg),
    "generate": lambda cfg, p: __import__(
        "ray_tpu.models", fromlist=["prefill"]).prefill(
            p, cfg, jnp.zeros((1, 4), jnp.int32), 8),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_refused_at_construction_with_the_reason(model, what):
    _, _, cfg, params = model
    with pytest.raises((ValueError, NotImplementedError),
                       match="stack of unlike layers|recurrent"):
        REFUSALS[what](cfg, params)


@pytest.mark.parametrize("call", ["prefill_only", "import_kv_pages",
                                  "begin_kv_import", "export_kv_pages"])
def test_kv_transfer_is_refused_at_the_call(model, call):
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    req = Request(request_id="x", prompt=[3, 4, 5], max_tokens=2,
                  prefill_only=(call == "prefill_only"))
    try:
        if call == "prefill_only":
            eng.add_request(req)
        elif call == "import_kv_pages":
            eng.import_kv_pages(req, {})
        elif call == "begin_kv_import":
            assert eng.begin_kv_import(req, 3, {}) is False
        else:
            with pytest.raises(ValueError, match="state beside its pages"):
                eng.export_kv_pages(req)
            return
        assert req.done.is_set() and "state beside its pages" in req.error
    finally:
        eng.stop()


def test_a_disaggregated_role_is_refused():
    from ray_tpu.serve.llm import LLMServer

    with pytest.raises(ValueError, match="serve it colocated"):
        LLMServer._target(model_name="tiny-sambay", role="prefill")


def test_registered_configs_count_and_segment():
    big = get_config("phi4-mini-flash")
    assert round(big.param_count() / 1e9, 2) == 3.85
    assert big.segments() == (
        (0, ("mamba", "window"), 8), (16, ("mamba",), 1),
        (17, ("full",), 1), (18, ("gmu", "cross"), 7))
    tiny = get_config("tiny-sambay")
    params = init_params(tiny, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == tiny.param_count()
    assert [r for _, _, r in tiny.segments()] == [3, 1, 1, 2]
    # the one-block models' counts, a tied LayerNorm'd one included; by
    # derivation each is the stack whose every layer is "attn": ONE scan
    for name in ("tiny-gpt2", "tiny-llama", "tiny-moe"):
        cfg = get_config(name)
        tree = init_params(cfg, jax.random.PRNGKey(0))
        assert sum(a.size for a in jax.tree.leaves(tree)) == cfg.param_count()
        assert cfg.layer_kinds == ("attn",) * cfg.n_layers
        assert cfg.segments() == ((0, ("attn",), cfg.n_layers),)
        assert (cfg.count("attn"), cfg.count("window")) == (cfg.n_layers, 0)
        assert stack.new_engine_state(cfg, 2, PAGE, "float32", "float32") == {}


# -- the one-block families: the "attn" kind -----------------------------------


@pytest.mark.parametrize("mode", ["chunk", "decode", "verify"])
@pytest.mark.parametrize("name", ["tiny-llama", "tiny-moe"])
def test_the_attn_kind_agrees_with_the_contiguous_reference(name, mode):
    """`stack.run_paged` over a page pool, in each of the modes the engine
    and the speculative programs build, against the contiguous-cache
    reference (`transformer.prefill` / `decode_step`, which the serve path
    no longer calls): a 13-token prompt in two chunks of 8, the last one
    padded, then three more tokens one at a time or as one verify span."""
    from ray_tpu.models import decode_step, prefill
    from ray_tpu.models.transformer import _head_logits

    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(30)
    T, C, more, max_len = 13, 8, 3, 32
    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, T + more), jnp.int32)
    layers, kvh, hd = cfg.cache_dims
    pools = (jnp.zeros(pool_shape(layers, 9, PAGE, kvh, hd), jnp.float32),) * 2
    table = jnp.arange(1, 9, dtype=jnp.int32)  # page 0 is the trash page

    def head(x):  # every row's logits
        return _head_logits(x, lambda x: x, params, cfg, "btd,dv->btv")

    def chunk(pools, start):
        padded = jnp.zeros((C,), jnp.int32).at[:min(C, T - start)].set(
            tokens[start:min(start + C, T)])
        x, k, v, state = stack.run_paged(
            params, padded[None], cfg,
            stack.Seq(cfg, chunk=(jnp.int32(start), table), page_size=PAGE),
            pools)
        assert state == {}  # pages are all there is
        return head(x)[0], (k, v)

    first, pools = chunk(pools, 0)
    second, pools = chunk(pools, C)
    if mode == "chunk":  # the prompt's logits, position by position
        want = np.stack([np.asarray(prefill(
            params, cfg, tokens[None, :T], max_len,
            last_index=jnp.asarray([t]))[0][0]) for t in range(T)])
        got = np.concatenate([first, second[:T - C]])
        np.testing.assert_allclose(got, want, atol=LOGPROB_TOL, rtol=0)
        return
    # the reference: the contiguous cache stepped through the next tokens
    _, cache = prefill(params, cfg, tokens[None, :T], max_len)
    want = []
    for i in range(more):
        logits, cache = decode_step(params, cfg, cache, tokens[None, T + i],
                                    jnp.asarray([T + i]))
        want.append(np.asarray(logits[0]))
    positions, tables = jnp.asarray([T, 0]), jnp.stack([table, table * 0])
    if mode == "decode":  # slot 1 is idle: position 0, the trash page
        got = []
        for i in range(more):
            feed = jnp.stack([tokens[T + i], jnp.int32(0)])[:, None]
            x, k, v, _ = stack.run_paged(
                params, feed, cfg,
                stack.Decode(cfg, positions + jnp.asarray([i, 0]), tables,
                             PAGE), pools)
            pools = (k, v)
            got.append(np.asarray(head(x)[0, 0]))
    else:  # one span of 3 for slot 0, of which the last row is no draft
        feed = jnp.stack([tokens[T:T + more], jnp.zeros((more,), jnp.int32)])
        x, k, v, _ = stack.run_paged(
            params, feed, cfg,
            stack.Verify(cfg, positions, tables, PAGE,
                         jnp.asarray([more - 2, 0])), pools)
        got, want = np.asarray(head(x)[0, :more - 1]), want[:more - 1]
        # a row past the slot's drafts wrote the trash page, not its own
        # (where the padded chunk's rows still are)

        def rewritten(pos):
            at = (slice(None), 0, table[pos // PAGE], pos % PAGE)
            return bool((k[at] != pools[0][at]).any())

        assert rewritten(T + more - 2) and not rewritten(T + more - 1)
    np.testing.assert_allclose(np.stack(got), np.stack(want),
                               atol=LOGPROB_TOL, rtol=0)


def test_the_serve_path_holds_no_layer_body_and_no_family_fork():
    """serve/engine.py and serve/spec_decode.py reach the layers through
    models/stack.py alone: no projection, rotary turn or FFN is written
    out there, and nothing reads `is_stack` but the two refusals (state
    beside pages that speculation and the KV wire cannot carry, and no
    sharding rules yet)."""
    import inspect
    import re

    from ray_tpu.serve import engine, spec_decode

    refusals = "".join(inspect.getsource(f) for f in (
        InferenceEngine._refuse_for_stack, InferenceEngine._refuse_kv_transfer))
    for module in (engine, spec_decode):
        src = inspect.getsource(module)
        for body in ('lp["wq"]', "apply_rope", "_dense_ffn", "_moe_ffn"):
            assert body not in src, (module.__name__, body)
        assert "run_paged" in src
        forks = len(re.findall(r"is_stack|\b_stack\b", src))
        assert forks == (2 if module is engine else 0), module.__name__
    assert len(re.findall(r"is_stack", refusals)) == 2
    assert "stack.prefill" in inspect.getsource(InferenceEngine._prefill_fn)
    for gone in ("_ffn", "_state_args"):
        assert not hasattr(engine, gone) and not hasattr(InferenceEngine, gone)


# -- the one-block families serve what they served ---------------------------

PARENT_OUTPUTS = {  # tokens and log-probabilities on PR 29's parent (57b8106),
    # whose pool was head-major: the layout moved, the served tokens did not
    "tiny-gpt2": [
        ([427, 427, 427, 427, 427, 427, 427, 427, 427],
         [-5.314390, -5.283927, -5.245603, -5.267851, -5.290354,
          -5.327097, -5.301139, -5.347447, -5.210656]),
        ([274, 274, 274, 274, 274, 274, 274, 274, 274],
         [-5.410497, -5.400505, -5.380394, -5.441947, -5.390732,
          -5.419333, -5.390093, -5.390194, -5.435591]),
        ([11, 11, 11, 11, 11, 11, 11, 11, 11],
         [-5.176742, -5.238420, -5.180849, -5.133516, -5.100941,
          -5.097808, -5.153522, -5.196352, -5.203264]),
    ],
    "tiny-llama": [
        ([231, 309, 161, 456, 280, 341, 491, 339, 84],
         [-5.848947, -5.722449, -5.737854, -5.777827, -5.740414,
          -5.796518, -5.821206, -5.766144, -5.717344]),
        ([440, 56, 43, 165, 324, 222, 274, 110, 10],
         [-5.829481, -5.763159, -5.795936, -5.857896, -5.762723,
          -5.766430, -5.767007, -5.809033, -5.855258]),
        ([245, 145, 426, 19, 460, 143, 314, 119, 361],
         [-5.803083, -5.783049, -5.740509, -5.798011, -5.782959,
          -5.808139, -5.794032, -5.711067, -5.782024]),
    ],
    "tiny-moe": [
        ([149, 141, 355, 96, 422, 109, 305, 334, 374],
         [-5.797878, -5.840640, -5.826507, -5.806545, -5.799323,
          -5.774203, -5.856468, -5.843260, -5.767271]),
        ([110, 254, 171, 249, 68, 76, 97, 134, 225],
         [-5.778585, -5.845037, -5.695619, -5.714362, -5.864853,
          -5.754840, -5.714167, -5.773575, -5.787898]),
        ([245, 145, 426, 331, 462, 255, 411, 413, 430],
         [-5.803912, -5.818085, -5.748591, -5.789080, -5.769641,
          -5.793132, -5.780172, -5.711228, -5.791537]),
    ],
}


@pytest.mark.parametrize("name", sorted(PARENT_OUTPUTS))
def test_old_families_serve_the_parents_tokens_and_logprobs(name):
    cfg = get_config(name)
    params = init_params(cfg, jax.random.PRNGKey(7))
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_batch_size=4, page_size=4, max_pages=64, max_seq_len=96,
        prefill_buckets=(8, 16), prefill_chunk=8, decode_span=4, busy_span=2))
    rng = np.random.default_rng(28)
    got = []
    try:
        for T in (5, 13, 22):  # the bucket path, then the chunked one twice
            got.append(eng.generate(rng.integers(3, cfg.vocab_size, T).tolist(),
                                    max_tokens=9))
    finally:
        eng.stop()
    for r, (tokens, logprobs) in zip(got, PARENT_OUTPUTS[name]):
        assert r["token_ids"] == tokens
        # the XLA reference sums in another order since the pool's rows
        # hold a token's heads side by side: a few ulps of float32
        np.testing.assert_allclose(r["logprobs"], logprobs, atol=2e-6, rtol=0)
