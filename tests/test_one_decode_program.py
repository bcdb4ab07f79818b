"""A span's length is no program (PR 53): the decode program takes its step
count as an argument (a loop to a traced bound that writes rows [:n] of a
[K, B] pair), where two lengths x two samplers were four programs, and the
two that are left, one a sampler, trace the layers once between them (the
step is an inner jit). Held here to the programs they replaced: a static
`lax.scan` of the span's length over the engine's own step body
(`static_span`, which tests/test_tpu_compile.py compiles for a described
chip too)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.core.metrics import registry
from ray_tpu.models import get_config, init_params, stack
from ray_tpu.serve import engine as engine_mod
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request
from ray_tpu.util import tracing

B, K, PAGE = 4, 8, 4
MODELS = ["tiny-llama", "tiny-moe", "tiny-olmo-hybrid"]


def static_span(eng, n, sampler):
    """A program PR 53 replaced, rebuilt from the engine's own step
    (`_decode_step`): a static scan of `n` steps with the sampler `"plain"`
    or `"sort"` (the `_adv` programs'). Takes the decode program's
    positional arguments through `state`; donates nothing. -> (seq, logps [n, B], k_pages, v_pages,
    state, (tokens, positions))."""
    cfg = eng.cfg

    def program(params, k_pages, v_pages, tokens, positions, tables, temps,
                top_ps, top_ks, key, state):
        window_tables = None
        if cfg.window_paged:
            tables, window_tables = tables

        def sample(logits, i):
            ki = jax.random.fold_in(key, i)
            if sampler == "sort":
                return engine_mod._device_sample_topk_topp(
                    logits, temps, top_ps, top_ks, ki)
            return engine_mod._sample_plain(logits, temps, ki)

        state = dict(state or {})
        if cfg.counts_choices:
            state["choices"] = jnp.zeros((2,), jnp.float32)
        if eng._steps_visit:
            state["touched"] = jnp.zeros((1,), jnp.float32)
        (tokens, positions, k_pages, v_pages, state), (seq, logps) = \
            jax.lax.scan(
                eng._decode_step(params, tables, window_tables, sample),
                (tokens, positions, k_pages, v_pages, state), jnp.arange(n))
        return seq, logps, k_pages, v_pages, state, (tokens, positions)

    return jax.jit(program)


@pytest.fixture(scope="module", params=MODELS)
def built(request):
    """An engine of each kind (dense, sparse experts whose steps visit, a
    hybrid with state beside its pages) whose threads never start, with a
    pool and a state of noise: every page and every slot holds something."""
    cfg = get_config(request.param)
    key = jax.random.PRNGKey(0)
    params = (stack.init_params if cfg.is_stack else init_params)(cfg, key)
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_batch_size=B, page_size=PAGE, max_pages=40, max_seq_len=64,
        prefill_buckets=(8, 16), prefill_chunk=16, cache_dtype="float32",
        decode_span=K, busy_span=4))
    eng._ensure_loop = lambda: None
    noise = iter(jax.random.split(jax.random.PRNGKey(1), 64))

    def fill(a):
        return 0.3 * jax.random.normal(next(noise), a.shape, a.dtype)

    pools = jax.tree.map(fill, (eng.k_pages, eng.v_pages))
    state = jax.tree.map(fill, eng.state)
    yield eng, pools, state
    eng.stop()


def _batch(eng, temps, top_ps, top_ks):
    """A batch of B rows at unlike positions over pages of their own."""
    pps = eng.ecfg.pages_per_seq
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(3, eng.cfg.vocab_size, B), jnp.int32)
    positions = jnp.asarray([5, 9, 14, 2], jnp.int32)
    tables = np.zeros((B, pps), np.int32)
    for i in range(B):  # six pages a row: 24 tokens, and a span ends at 22
        tables[i, :6] = 1 + 6 * i + np.arange(6)
    return (tokens, positions, jnp.asarray(tables),
            jnp.asarray(temps, jnp.float32), jnp.asarray(top_ps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32), jax.random.PRNGKey(11))


def _program(eng, n, advanced, pools, batch, state):
    """The sampler's program at `n` over copies of the pool and the state
    (it takes both by donation), from a carry the batch's rows all
    replace."""
    k, v = jax.tree.map(jnp.copy, pools)
    carry = (jnp.full((B,), 7, jnp.int32), jnp.full((B,), 3, jnp.int32),
             jnp.ones((B,), bool))
    return eng._decode(n, advanced)(eng.params, k, v, *batch,
                                    jax.tree.map(jnp.copy, state), carry)


def _same(got, want):
    flat_got, tree = jax.tree.flatten(got)
    flat_want, want_tree = jax.tree.flatten(want)
    assert tree == want_tree
    for a, b in zip(flat_got, flat_want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


GREEDY_AND_SAMPLED = ([0.0, 0.8, 1.3, 0.0], [1.0] * 4, [0] * 4)
ONE_TOP_P_ROW = ([0.0, 0.8, 1.3, 0.7], [1.0, 1.0, 0.6, 1.0], [0, 0, 0, 0])
ONE_TOP_K_ROW = ([0.9, 0.8, 0.0, 0.7], [1.0] * 4, [0, 5, 0, 0])


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("how, sampler", [
    (GREEDY_AND_SAMPLED, "plain"), (ONE_TOP_P_ROW, "sort"),
    (ONE_TOP_K_ROW, "sort")], ids=["plain", "top_p", "top_k"])
def test_n_steps_of_the_program_are_the_static_scan_of_n(
        built, n, how, sampler):
    """Bit for bit: tokens, log-probabilities, pool, state, the counts'
    rows and the carry the next span starts from, for each sampler's
    program (the plain one draws what the plain sampler draws for the key;
    the other passes every row through the sort sampler, a greedy row and
    a row that cuts nothing among them)."""
    eng, pools, state = built
    batch = _batch(eng, *how)
    seq, logps, k, v, st, carry = _program(eng, n, sampler == "sort", pools,
                                           batch, state)
    w_seq, w_logps, w_k, w_v, w_st, w_carry = static_span(eng, n, sampler)(
        eng.params, *pools, *batch, state)
    seq, logps = np.asarray(seq), np.asarray(logps)
    assert seq.shape == (K, B) and seq.dtype == np.int32
    np.testing.assert_array_equal(seq[:n], np.asarray(w_seq))
    np.testing.assert_array_equal(logps[:n], np.asarray(w_logps))
    # the rows no step wrote stay zero, and the counts ride behind row K
    assert not seq[n:].any() and not logps[n:K].any()
    counts = [np.asarray(w_st.pop(name)) for name in ("choices", "touched")
              if name in w_st]
    assert logps.shape == (K + len(counts), B)
    for row, want in zip(logps[K:], counts):
        np.testing.assert_array_equal(row[:want.shape[0]], want)
        assert not row[want.shape[0]:].any()
    if eng._steps_visit:
        assert counts[-1][0] > 0  # the steps did visit experts
    _same((k, v, st, carry), (w_k, w_v, w_st, w_carry))
    if n == 2:  # the noise is something: the span moved pool and state
        assert not np.array_equal(np.asarray(k), np.asarray(pools[0]))
        assert all(not np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(jax.tree.leaves(st),
                                   jax.tree.leaves(state)))


def test_the_two_samplers_draw_apart_for_one_key(built):
    """Why a batch without a row that cuts must take the plain program: the
    sort sampler draws OTHER tokens for the same key on a sampled row (its
    noise falls on the sorted order), and the same on a greedy one."""
    eng, pools, state = built
    batch = _batch(eng, [0.0, 0.8, 0.0, 0.0], [0.5, 1.0, 0.9, 1.0],
                   [0, 0, 3, 0])
    plain = np.asarray(_program(eng, K, False, pools, batch, state)[0])
    sort = np.asarray(_program(eng, K, True, pools, batch, state)[0])
    assert not np.array_equal(plain[:, 1], sort[:, 1])  # the sampled row
    np.testing.assert_array_equal(plain[0, [0, 2, 3]], sort[0, [0, 2, 3]])


def test_a_span_outside_the_programs_rows_is_refused(built):
    eng, _, _ = built
    for n in (0, K + 1, 16):
        with pytest.raises(ValueError, match=f"spans of 1 to {K}"):
            eng._decode(n)
    for advanced in (False, True):  # a program a sampler, whatever the span
        assert eng._decode(1, advanced).__wrapped__ \
            is eng._decode(K, advanced).__wrapped__
    assert eng._decode(K).__wrapped__ is not eng._decode(K, True).__wrapped__


def test_the_two_programs_trace_the_layers_once(monkeypatch):
    """The step's layers and head are an inner jit (`_forward`), whose trace
    jax keeps by its arguments' shapes whoever calls it: lowering both
    samplers' programs runs the layers' Python once. (Each of the four
    programs PR 53 replaced traced them for itself: 3 to 3.4 s apiece at
    granite's sizes on the chip's host.)"""
    from ray_tpu.models import stack as stack_mod

    cfg = get_config("tiny-olmo-hybrid")
    traced, run_paged = [], stack_mod.run_paged

    def counting(params, tokens, cfg, mode, *rest):
        traced.append(type(mode).__name__)
        return run_paged(params, tokens, cfg, mode, *rest)

    monkeypatch.setattr(stack_mod, "run_paged", counting)
    programs = InferenceEngine.abstract(cfg, EngineConfig(
        max_batch_size=B, page_size=PAGE, max_pages=16, max_seq_len=32,
        prefill_chunk=16, cache_dtype="float32")).programs(
            jax.eval_shape(lambda k: stack.init_params(cfg, k),
                           jax.random.PRNGKey(0)), buckets=())
    texts = [programs[name].lower().as_text()
             for name in ("decode_span", "decode_span_adv")]
    assert traced == ["Decode"]
    assert "stablehlo.sort" not in texts[0] and "stablehlo.sort" in texts[1]


# -- the loop -----------------------------------------------------------------


def _tiny(**kw):
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0))
    ecfg = dict(max_batch_size=B, page_size=PAGE, max_pages=64,
                max_seq_len=96, prefill_buckets=(8, 16), prefill_chunk=16,
                decode_span=K, busy_span=2)
    ecfg.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**ecfg)), cfg


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size, n).tolist() for n in lens]


def test_rows_past_the_span_reach_no_request_and_a_short_span_frees_pages():
    """Every dispatched span's rows [n:K] are overwritten with a token no
    model draws before the host reads them: no request receives one, every
    answer is the one-token-a-step engine's, a sequence that ends inside a
    short span (3 of its 2 + 2 tokens) gives its pages back once that span
    is read, and both lengths were dispatched."""
    eng, cfg = _tiny()
    eng._ensure_loop = lambda: None
    POISON, lengths = -7, []
    decode = eng._decode

    def poisoned(n, advanced=False):
        program = decode(n, advanced)

        def call(*args):
            seq, logps, *rest = program(*args)
            lengths.append(n)
            assert not advanced  # greedy requests: the plain program
            return (seq.at[n:].set(POISON), logps.at[n:K].set(np.nan), *rest)

        return call

    eng._decode = poisoned
    free = eng.stats()["free_pages"]
    prompts = _prompts(cfg, (5, 7, 6, 40))  # the last one is chunked
    budgets = (4, 21, 12, 9)  # 4: a first token and 3 of a span of 2 + 2
    reqs = [Request(f"r{i}", p, max_tokens=m)
            for i, (p, m) in enumerate(zip(prompts, budgets))]
    for r in reqs[:3]:
        eng.add_request(r)
    eng._prefill_batch([eng.pending.get() for _ in reqs[:3]])
    eng._iterate()  # the three go out: nothing waits, the long span
    eng.add_request(reqs[3])  # a prompt waits: the busy span from here on
    eng._prefill_batch([eng.pending.get()])
    held = None
    for _ in range(40):
        eng._iterate()
        if reqs[0].done.is_set() and held is None:
            held = eng.stats()["free_pages"]
        if all(r.done.is_set() for r in reqs):
            break
    eng._drain()
    assert all(r.done.is_set() for r in reqs)
    assert set(lengths) == {2, K} and lengths[0] == K
    for r, m in zip(reqs, budgets):
        assert len(r.output) == m and POISON not in r.output
        assert all(np.isfinite(r.output_logprobs))
    # the short request's pages came back while the others still decoded
    assert held is not None and held > free - sum(
        -(-(len(p) + m) // PAGE) for p, m in zip(prompts[1:], budgets[1:])) - 1
    assert eng.stats()["free_pages"] == free
    eng.stop()
    want, _ = _tiny(decode_span=1, adaptive_span=False)
    try:
        for r, p, m in zip(reqs, prompts, budgets):
            assert r.output == want.generate(p, max_tokens=m)["token_ids"]
    finally:
        want.stop()


def _compiles():
    return sum(v for _n, _t, v in registry.get("xla_compiles").samples())


def _span_steps():
    return {tuple(t): v for _n, t, v in registry.get(
        "serve_decode_span_steps").samples()}


def test_nothing_compiles_after_warmup_through_spans_and_samplers():
    """`warmup` and one request a prompt (the bucket path writes a shape a
    count of pages) compile what the engine runs; after that overlapping
    requests (spans of both lengths), the first `top_k` request and the
    first `top_p` one compile nothing, and a batch takes the sort sampler's
    program while it holds a row that cuts and the plain one's otherwise."""
    tracing.watch_compiles()
    eng, cfg = _tiny()
    try:
        eng.warmup()
        prompts = _prompts(cfg, (5, 12, 40, 7, 30, 9), seed=2)
        for p in prompts:  # both prefill paths and every count of pages
            eng.generate(p, max_tokens=3)
        eng.generate(prompts[0], max_tokens=3, temperature=0.7)
        programs = [eng._decode(K, adv).__wrapped__ for adv in (False, True)]
        assert [p._cache_size() for p in programs] == [1, 1]
        compiled, steps = _compiles(), _span_steps()
        lengths, decode = [], eng._decode
        eng._decode = lambda n, adv=False: (
            lengths.append((n, adv)), decode(n, adv))[1]
        how = [{}, {"temperature": 0.9, "top_k": 5}, {},
               {"temperature": 0.8, "top_p": 0.7}, {"temperature": 1.1}, {}]
        out = {}
        threads = [threading.Thread(target=lambda i=i: out.__setitem__(
            i, eng.generate(prompts[i], max_tokens=20, **how[i])))
            for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert sorted(out) == list(range(6))
        assert all(len(o["token_ids"]) == 20 for o in out.values())
        assert {2, K} <= {n for n, _ in lengths}
        assert {adv for _, adv in lengths} == {False, True}
        assert sum(_span_steps().values()) - sum(steps.values()) \
            == sum(n for n, _ in lengths)
        assert _compiles() == compiled
        assert [p._cache_size() for p in programs] == [1, 1]
    finally:
        eng.stop()
