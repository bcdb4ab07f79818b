"""The second chunk program (serve/engine.py `_wide_chunk`): a model whose
chunk runs each expert over the rows that chose it has a program of twice
`prefill_chunk` rows beside today's, a prompt with more than
`prefill_chunk` tokens left takes it, and it serves what chunks of
`prefill_chunk` rows serve. A dense model, a sharded mesh and a
speculating engine have no such program."""

import dataclasses

import jax
import numpy as np
import pytest

from ray_tpu.core.metrics import registry
from ray_tpu.models import get_config, init_params, stack
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

LOGPROB_TOL = 2e-5
# routed experts, nothing dropped: one-block attention with a prefix cache
# (`tiny-moe` as registered drops rows: capacity factor 1.25), short
# convolutions beside attention, window layers in allocated pages beside
# full ones (window 16), latent attention with a prefix cache
EXPERTS = {
    "tiny-moe": dict(capacity_factor=2.0),
    "tiny-lfm2": {},
    "tiny-smallthinker": {},
    "tiny-longcat-flash": {},
}
DENSE = ("tiny-llama", "tiny-sambay", "tiny-olmo-hybrid",
         "tiny-granite-hybrid")


def _model(name, **changes):
    cfg = dataclasses.replace(get_config(name), dtype="float32",
                              max_seq_len=2048, **changes)
    init = stack.init_params if cfg.is_stack else init_params
    return cfg, init(cfg, jax.random.PRNGKey(46))


def _engine(cfg, params, **kw):
    ecfg = dict(max_batch_size=2, page_size=4, max_pages=160, max_seq_len=128,
                prefill_buckets=(8, 16), prefill_chunk=16, decode_span=4,
                busy_span=4, cache_dtype="float32", prefix_caching=False)
    if cfg.window_paged:
        ecfg["max_window_pages"] = 64
    ecfg.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**ecfg))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(3, 200, n).tolist()


def _calls(rows):
    return registry.get("serve_chunk_calls").get({"rows": str(rows)})


def _counter(name):
    return sum(v for _s, _t, v in registry.get(name).samples())


@pytest.fixture(scope="module")
def models():
    made = {}

    def model(name):
        if name not in made:
            made[name] = _model(name, **EXPERTS.get(name, {}))
        return made[name]

    return model


@pytest.fixture(scope="module")
def wide_engines(models):
    """One engine a model at the cells' chunk of 256 rows, pages of 16."""
    made = {}

    def engine(name):
        if name not in made:
            cfg, params = models(name)
            made[name] = _engine(
                cfg, params, page_size=16, max_pages=256, max_seq_len=2048,
                prefill_chunk=256, max_window_pages=160)
        return made[name]

    yield engine
    for eng in made.values():
        eng.stop()


def _same_answer(got, want):
    assert got["token_ids"] == want["token_ids"]
    assert np.abs(np.asarray(got["logprobs"])
                  - np.asarray(want["logprobs"])).max() < LOGPROB_TOL


# -- the same answer ---------------------------------------------------------


@pytest.mark.parametrize("tokens, calls", [
    (257, (1, 0)), (512, (1, 0)), (513, (1, 1)), (700, (1, 1)),
    (1300, (3, 0))])
@pytest.mark.parametrize("name", sorted(EXPERTS))
def test_wide_and_narrow_chunks_serve_the_same_answer(
        name, tokens, calls, wide_engines, monkeypatch):
    """A prompt of one token over a chunk, two whole chunks, one over, a
    tail, and five chunks (on the window-paged model the ring of 16 / 16 +
    512 / 16 = 33 pages wraps inside the second wide chunk): the tokens and
    log-probabilities of the same engine held to `prefill_chunk` rows."""
    eng = wide_engines(name)
    assert eng._wide == 512
    prompt = _prompt(tokens, seed=tokens)
    wide, narrow = _calls(512), _calls(256)
    got = eng.generate(prompt, max_tokens=6)
    assert (_calls(512) - wide, _calls(256) - narrow) == calls
    monkeypatch.setattr(eng, "_wide", 0)
    wide = _calls(512)
    want = eng.generate(prompt, max_tokens=6)
    assert _calls(512) == wide
    _same_answer(got, want)


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-longcat-flash"])
def test_a_prefix_hit_in_front_of_a_wide_chunk(name, models):
    """The second ask shares 300 tokens with the first: a hit of its 18
    whole pages, then a wide chunk from token 288 (no multiple of either
    program's rows) and the tail."""
    cfg, params = models(name)
    sizes = dict(page_size=16, max_pages=256, max_seq_len=2048,
                 prefill_chunk=256)
    first, second = _prompt(700, seed=1), _prompt(900, seed=2)
    second[:300] = first[:300]
    cached = _engine(cfg, params, prefix_caching=True, **sizes)
    plain = _engine(cfg, params, **sizes)
    plain._wide = 0
    try:
        cached.generate(first, max_tokens=2)
        hits = _counter("serve_prefix_cache_hit_tokens")
        wide, narrow = _calls(512), _calls(256)
        got = cached.generate(second, max_tokens=6)
        assert _counter("serve_prefix_cache_hit_tokens") - hits == 288
        assert (_calls(512) - wide, _calls(256) - narrow) == (1, 1)
        _same_answer(got, plain.generate(second, max_tokens=6))
    finally:
        cached.stop(), plain.stop()


# -- which engines have the program -------------------------------------------


def _tp2():
    from ray_tpu.comm.mesh import MeshSpec, build_mesh

    return build_mesh(MeshSpec.create(tp=2), devices=jax.devices("cpu")[:2])


@pytest.mark.parametrize("name, changes, engine, wide", [
    *((name, EXPERTS[name], {}, 32) for name in sorted(EXPERTS)),
    *((name, {}, {}, 0) for name in DENSE),
    ("tiny-moe", {}, {}, 0),  # as registered: rows can be dropped
    ("tiny-moe", EXPERTS["tiny-moe"], dict(mesh=_tp2), 0),
    ("tiny-moe", EXPERTS["tiny-moe"], dict(busy_span=1), 0),
    ("tiny-moe", EXPERTS["tiny-moe"], dict(chunked_prefill=False,
                                           prefix_caching=False), 0),
    ("tiny-moe", EXPERTS["tiny-moe"],
     dict(speculation={"mode": "ngram", "num_speculative_tokens": 2}), 0),
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_rule_is_the_models_shapes_and_the_mesh(name, changes, engine,
                                                    wide):
    """Routed experts that drop nothing on an unsharded mesh have the wide
    program; a dense model, a capacity that drops, a sharded mesh, a turn
    of one chunk, no chunked prefill and speculation do not."""
    engine = dict(engine)
    mesh = engine.pop("mesh", lambda: None)()
    cfg = dataclasses.replace(get_config(name), **changes)
    if cfg.window_paged:  # its window layers' pages: room for the ring
        engine.update(max_window_pages=40, prefill_buckets=(8, 16))
    ecfg = EngineConfig(page_size=4, prefill_chunk=16, **engine)
    bare = InferenceEngine.abstract(cfg, ecfg, mesh)
    assert bare._wide == wide
    assert ("chunk_prefill_32" in dict.fromkeys(
        p.name for p in bare._programs(buckets=()))) == bool(wide)


@pytest.mark.parametrize("name", DENSE)
def test_a_dense_model_builds_no_wide_program(name, monkeypatch):
    """Warm-up and a prompt of three chunks ask for ONE chunk program, of
    `prefill_chunk` rows."""
    cfg, params = _model(name)
    eng = _engine(cfg, params)
    asked, build = set(), eng._chunk_fn

    def spy(rows, export=False):
        asked.add((rows, export))
        return build(rows, export)

    monkeypatch.setattr(eng, "_chunk_fn", spy)
    try:
        assert eng._wide == 0
        eng.warmup(buckets=[])
        eng.generate(_prompt(40), max_tokens=3)
        assert asked == {(16, False)}
        assert build(16)._cache_size() == 1
    finally:
        eng.stop()


# -- warm-up ------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(EXPERTS))
def test_after_warmup_no_chunk_call_compiles(name, models):
    """Both chunk programs are compiled by `warmup`; prompts that take
    each of them find them there."""
    cfg, params = models(name)
    eng = _engine(cfg, params)
    try:
        eng.warmup(buckets=[])
        programs = [eng._chunk_fn(16), eng._chunk_fn(32)]
        assert [p._cache_size() for p in programs] == [1, 1]
        wide, narrow = _calls(32), _calls(16)
        for tokens in (20, 40, 70):
            eng.generate(_prompt(tokens, seed=tokens), max_tokens=3)
        # 20: one wide; 40: a wide and the tail; 70: two wide and the tail
        assert (_calls(32) - wide, _calls(16) - narrow) == (4, 2)
        assert [p._cache_size() for p in programs] == [1, 1]
    finally:
        eng.stop()


# -- the turn and the counters ------------------------------------------------


def _by_hand(eng, prompts, max_tokens=4):
    eng._ensure_loop = lambda: None
    reqs = [Request(request_id=f"r{i}", prompt=p, max_tokens=max_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    eng._prefill_batch([eng.pending.get() for _ in reqs])
    return reqs


@pytest.mark.parametrize("busy_span, prompts, turns", [
    # one prompt alone: a wide chunk a turn, then its tail
    (4, (90,), [[32], [32], [32]]),
    (4, (40,), [[32], [16]]),
    # a deep queue: `busy_span` chunks of 16 rows at the most, a wide one two
    # (the last prompt is alone in the queue: a chunk a turn)
    (4, (90, 90, 90, 90), [[32, 32]] * 5 + [[32], [32]]),
    # the second prompt's first chunk finds half a turn left
    (2, (40, 37, 52), [[32], [16, 16], [32], [32], [32]]),
    # an odd bound: a wide chunk, then what is left of the turn
    (3, (90, 40, 40), [[32, 16], [32, 16], [32, 16], [32], [16]]),
])
def test_a_turn_never_holds_more_than_busy_span_chunks_of_rows(
        busy_span, prompts, turns, models):
    """Rows of each `_advance_chunks` turn, in order: `busy_span x
    prefill_chunk` at the most, as many chunks as prompts wait, the oldest
    prompt's first; where a wide chunk does not fit the turn any more, a
    prompt with more than a chunk left takes `prefill_chunk` rows."""
    cfg, params = models("tiny-lfm2")
    eng = _engine(cfg, params, busy_span=busy_span, max_batch_size=4)
    ran, one = [], eng._advance_chunk

    def counted(room=None):
        rows = one(room)
        if rows:
            ran[-1].append(rows)
        return rows

    eng._advance_chunk = counted
    try:
        reqs = _by_hand(eng, [_prompt(n, seed=i)
                              for i, n in enumerate(prompts)])
        while eng._chunk_queue:
            ran.append([])
            eng._iterate()
        assert ran == turns
        assert all(sum(t) <= busy_span * 16 for t in ran)
        for _ in range(40):
            eng._iterate()
        assert all(r.done.is_set() and r.error is None for r in reqs)
    finally:
        eng.stop()


@pytest.mark.parametrize("tokens, wide, narrow", [
    (17, 1, 0), (32, 1, 0), (33, 1, 1), (45, 1, 1), (90, 3, 0), (110, 3, 1)])
def test_chunk_calls_are_counted_by_rows(tokens, wide, narrow, models):
    """`serve_chunk_calls{rows}` by program, `serve_chunk_rows` and
    `serve_chunk_padding_tokens` by what the programs ran."""
    cfg, params = models("tiny-lfm2")
    eng = _engine(cfg, params)
    before = (_calls(32), _calls(16), _counter("serve_chunk_rows"),
              _counter("serve_chunk_padding_tokens"))
    try:
        reqs = _by_hand(eng, [_prompt(tokens)], max_tokens=2)
        for _ in range(12):
            eng._iterate()
        assert reqs[0].done.is_set() and reqs[0].error is None
    finally:
        eng.stop()
    rows = 32 * wide + 16 * narrow
    assert (_calls(32) - before[0], _calls(16) - before[1]) == (wide, narrow)
    assert _counter("serve_chunk_rows") - before[2] == rows
    assert _counter("serve_chunk_padding_tokens") - before[3] == rows - tokens
