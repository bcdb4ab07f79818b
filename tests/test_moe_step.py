"""The expert product of a decode step (ops/moe.py `expert_step`,
models/transformer.py `moe_ffn_step`): the experts that a live row chose and
no others, against the form that runs every expert
(`_moe_ffn_dropless_ids`); which programs take it (a step that knows its
live rows) and which keep the program they had; what the engine counts."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from ray_tpu.models import get_config, init_params, stack
from ray_tpu.models import transformer as tr
from ray_tpu.ops import moe
from ray_tpu.serve.engine import EngineConfig, InferenceEngine, Request

TOL = 2e-5
D, F, ROWS, LAYERS, LAYER = 128, 256, 8, 3, 1


@pytest.fixture
def kernel(monkeypatch):
    """The Pallas kernel in interpret mode, as the other kernels' tests."""
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


def _formulation(name):
    """-> (cfg, whether the gate is handed in)."""
    if name == "8-top-2-swiglu-softmax":
        return dataclasses.replace(
            get_config("tiny-moe"), num_experts=8, num_selected_experts=2), False
    if name == "32-top-4-sigmoid-bias":
        return dataclasses.replace(
            get_config("tiny-lfm2"), num_experts=32,
            num_selected_experts=4), False
    if name == "64-top-6-reglu-gate-handed-in":
        return dataclasses.replace(
            get_config("tiny-smallthinker"), num_experts=64,
            num_selected_experts=6), True
    assert name == "16-held-of-768-wide-router-identity-experts"
    return dataclasses.replace(
        get_config("tiny-longcat-flash"), num_experts=16,
        num_selected_experts=12, n_routed_experts=512, experts_first=40,
        experts_zero=256, capacity_factor=16 / 12), False


FORMULATIONS = ["8-top-2-swiglu-softmax", "32-top-4-sigmoid-bias",
                "64-top-6-reglu-gate-handed-in",
                "16-held-of-768-wide-router-identity-experts"]
LIVE = {"none": [], "one": [2], "a-few": [0, 2, 3], "all": list(range(ROWS))}


def _layer(cfg, seed=0):
    """One layer's leaves as `run_stack` hands them to a step: the stacks
    of LAYERS layers and the layer's index, and the same layer's slices
    for the form that runs every expert."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    E, W = cfg.num_experts, cfg.router_width
    bias = 0.3 * jax.random.normal(ks[1], (W,), jnp.float32)
    router = jax.random.normal(ks[0], (D, W), jnp.float32)
    if W != E:
        # scores near 1 / W apart by rows, and a bias of their size that
        # favours the held experts: a row's choices fall on two or three of
        # them, on identity experts and on experts held elsewhere
        held = jnp.arange(cfg.experts_first, cfg.experts_first + E)
        router, bias = 0.05 * router, (bias / 600).at[held].add(1.5e-3)
    stacks = {
        "w_in": jax.random.normal(ks[2], (LAYERS, E, D, F)) / D ** 0.5,
        "w_gate": jax.random.normal(ks[3], (LAYERS, E, D, F)) / D ** 0.5,
        "w_out": jax.random.normal(ks[4], (LAYERS, E, F, D)) / F ** 0.5}
    lp = {"router": router, "router_bias": bias}
    x = jax.random.normal(ks[5], (ROWS, 1, D), jnp.float32)
    other = jax.random.normal(ks[6], (ROWS, 1, D), jnp.float32)
    return (x, other, {**lp, "experts": (stacks, LAYER)},
            {**lp, **{n: w[LAYER] for n, w in stacks.items()}})


def _touched(ids, live, cfg):
    """What `_count_touched` counted from the router's choices before the
    kernel had a list: held experts that a live row's choices fell on."""
    first = cfg.experts_first
    ids = np.asarray(ids)[np.asarray(live)]
    return len({int(e) for e in ids.ravel()
                if first <= e < first + cfg.num_experts})


@pytest.mark.parametrize("live", list(LIVE))
@pytest.mark.parametrize("name", FORMULATIONS)
def test_the_step_is_the_dropless_sum_over_the_live_rows(name, live, kernel):
    cfg, handed = _formulation(name)
    x, other, step_lp, whole_lp = _layer(cfg)
    gate = tr._moe_gate(other, whole_lp, cfg) if handed else None
    mask = np.zeros(ROWS, bool)
    mask[LIVE[live]] = True
    want, _, want_ids = tr._moe_ffn_dropless_ids(x, whole_lp, cfg, gate)
    got, ids, visited = jax.jit(
        lambda x, lp, m: tr.moe_ffn_step(x, lp, cfg, gate, m))(
            x, step_lp, jnp.asarray(mask))
    np.testing.assert_array_equal(ids, want_ids)
    assert int(visited) == _touched(want_ids, mask, cfg)
    if live != "none":
        assert int(visited) > 0
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(want)[mask],
                               atol=TOL, rtol=0)
    if live == "none" and not cfg.experts_zero:
        # a span dispatched ahead whose rows all ended: zeros, no expert read
        assert not np.asarray(got).any()


def test_a_dead_slots_choice_touches_nothing(kernel):
    """Rows that hold no sequence choose experts too: none of them is
    visited, and the live row's result does not depend on them."""
    cfg, _ = _formulation("32-top-4-sigmoid-bias")
    x, _, step_lp, whole_lp = _layer(cfg, seed=3)
    mask = np.zeros(ROWS, bool)
    mask[2] = True
    _, _, ids = tr._moe_ffn_dropless_ids(x, whole_lp, cfg)
    assert len(set(np.asarray(ids).ravel())) > cfg.num_selected_experts
    got, _, visited = tr.moe_ffn_step(x, step_lp, cfg, None, jnp.asarray(mask))
    assert int(visited) == cfg.num_selected_experts
    alone, _, _ = tr.moe_ffn_step(
        jnp.where(mask[:, None, None], x, 7.0), step_lp, cfg, None,
        jnp.asarray(mask))
    np.testing.assert_allclose(got[2], alone[2], atol=TOL, rtol=0)


@pytest.mark.parametrize("hit", [[], [3], [0, 2, 3, 7], list(range(8))],
                         ids=["none", "one", "some", "all"])
def test_the_kernel_is_the_xla_form_with_the_combines_zeros(hit, kernel):
    """`expert_step` alone: the kernel against its XLA form, every row;
    the list holds the hit experts in their order and zeros after."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    E = 8
    w_in, w_gate = (jax.random.normal(k, (LAYERS, E, D, F)) * 0.1
                    for k in ks[:2])
    w_out = jax.random.normal(ks[2], (LAYERS, E, F, D)) * 0.1
    x = jax.random.normal(ks[3], (ROWS, D))
    c = jax.random.uniform(ks[4], (ROWS, E))
    mask = np.zeros(E, bool)
    mask[hit] = True
    order, count = moe.visit_list(jnp.asarray(mask))
    assert list(np.asarray(order)) == hit + [0] * (E - len(hit))
    assert int(count) == len(hit)
    args = (x, c, jnp.asarray(mask), w_in, w_gate, w_out, LAYER, jax.nn.silu)
    got, n = jax.jit(lambda *a: moe.expert_step(*a, jax.nn.silu))(*args[:-1])
    want, _ = moe.expert_step(*args, force_xla=True)
    assert int(n) == len(hit)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    if not hit:
        assert not np.asarray(got).any()


def test_a_block_is_whole_lane_tiles_that_divide_the_width():
    """The four cells' shapes: blocks within the budget, the width divided."""
    for D_, F_, want in ((4096, 14336, 512), (2048, 1792, 896),
                         (2560, 768, 768), (6144, 2048, 256)):
        assert moe.f_tile(D_, F_, 2) == want
        assert F_ % want == 0 and D_ * want * 2 <= 4 * 2 ** 20
    assert moe.f_tile(128, 256, 4) == 256
    assert moe.f_tile(8192, 128, 4, block_bytes=1) == 128  # one tile at least


# -- which programs take the step's form -------------------------------------


def test_only_a_step_that_knows_its_live_rows_visits():
    """The rule is the static shape a mode sees and the mesh: `Decode` at
    one token a row; not `Verify`, not a `Seq` (chunk, bucket, forward),
    not a mesh that shards a model axis, not a model without experts."""
    cfg = get_config("tiny-moe")
    tables = jnp.ones((2, 4), jnp.int32)
    at = jnp.zeros((2,), jnp.int32)
    decode = stack.Decode(cfg, at, tables, 4)
    assert decode.live_rows(1) is decode.live and decode.live_rows(2) is None
    assert stack.Verify(cfg, at, tables, 4, at).live_rows(1) is None
    assert stack.Seq(cfg).live_rows(1) is None
    assert tr.moe_step_visits(cfg, None)
    assert not tr.moe_step_visits(get_config("tiny-llama"), None)

    class Sharded:
        shape = {"tp": 2}

    assert not tr.moe_step_visits(cfg, Sharded())
    # sambay's layers are all dense; lfm2's stack leads with two dense ones
    assert not tr.moe_step_visits(get_config("tiny-sambay"), None)
    assert tr.moe_step_visits(get_config("tiny-lfm2"), None)


# sha256 of the StableHLO text of the programs that must NOT change, as this
# tree's parent (c4ad2b2) lowers them for the CPU at `highest` matmul
# precision, jax as pinned below (tests/test_smallthinker_model.py pins the
# accepted families' chunk and bucket programs and the dense families' decode
# programs the same way; these are the ones it lacks): the new family's chunk
# and bucket, and `Verify` and a training step with and without experts
PARENT_PROGRAMS = {
    ("tiny-smallthinker", "chunk"):
        "65df4485175fe14901acaafed3efe55e18f404861ccbc9aa4145a84c9aacffbc",
    ("tiny-smallthinker", "bucket"):
        "99163ff2441238c365bb37e4b94f53cc8f954bce9b37946d44269603d44122d1",
    ("tiny-moe", "verify"):
        "d3757c5f5b5cfb8b9197aa6ddaec8e90f1acc1251c8a48276541d071d5c42e14",
    ("tiny-moe", "train"):
        "3b3c9924d4d7ee999a31808dd0bcf82b8dfc8eaed4b85c3633356b85726fe7a5",
    ("tiny-llama", "verify"):
        "c6b1cc0a49ce4ea71c6f3014aeae0654b4d858206b738123ac99babed7ff9135",
    ("tiny-llama", "train"):
        "b83c51c1ac5d70406217e795c96235dc1e59af72ddf68774a44f592aaa64a056",
}
# the expert families' decode programs DID change (a step visits): tiny-moe's,
# tiny-lfm2's and tiny-longcat-flash's are re-pinned where they were pinned
# (tests/test_smallthinker_model.py, tests/test_longcat_flash_model.py); the
# newest family's is pinned here, this tree's own, so that a later change to
# it is one that is meant
DECODE_PROGRAMS = {
    "tiny-smallthinker":
        "b25c20e8ba6129a0ae5584d35968c89d4ba54efd46088d37193585b58f46124e",
}
LOWERED_WITH_JAX = "0.9.0"
PAGE = 4


def _bare_engine(name):
    """An engine object that builds programs and allocates nothing."""
    cfg = get_config(name)
    params = jax.eval_shape(lambda k: stack.init_params(cfg, k)
                            if cfg.is_stack else init_params(cfg, k),
                            jax.random.PRNGKey(0))
    kw = (dict(max_window_pages=40, prefill_buckets=(8, 16))
          if cfg.window_paged else {})
    eng = object.__new__(InferenceEngine)
    eng.cfg, eng.mesh, eng._tp, eng._prefill_cache = cfg, None, 1, {}
    eng.ecfg = EngineConfig(max_batch_size=2, page_size=PAGE, max_pages=16,
                            max_seq_len=32, prefill_chunk=16,
                            cache_dtype="float32", **kw)
    pool = eng.abstract_pool()
    return eng, params, pool, None if cfg.latent_cache else pool


def _lowered(name, program):
    from ray_tpu.serve import spec_decode

    eng, params, k_pool, v_pool = _bare_engine(name)
    cfg = eng.cfg
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    tables, table = i32(2, 8), i32(8)
    if cfg.window_paged:
        eng._ring = ring = eng._window_ring()
        tables, table = (tables, i32(2, ring)), (table, i32(ring))
        state = start = eng.abstract_state()
    else:
        state = jax.eval_shape(lambda: stack.new_engine_state(
            cfg, 2, PAGE, jnp.float32, jnp.float32))
        start = jax.eval_shape(
            lambda: stack.new_request_state(cfg, 1, jnp.float32))
    if program == "decode":
        return eng._build_decode()(4).lower(
            params, k_pool, v_pool, i32(2), i32(2), tables, f32(2), f32(2),
            i32(2), key, state,
            (i32(2), i32(2), jax.ShapeDtypeStruct((2,), jnp.bool_)))
    if program == "chunk":
        return eng._build_chunk_prefill()(16).lower(
            params, k_pool, v_pool, i32(16), i32(), table, i32(), start)
    if program == "bucket":
        return eng._prefill_fn(16, 1).lower(params, i32(1, 16), i32(1))
    if program == "verify":
        spec = object.__new__(spec_decode.SpecDecoder)
        spec.engine, spec.k = eng, 3
        return spec._build_verify()(False).lower(
            params, k_pool, v_pool, i32(2, 4), i32(2), i32(2, 8), i32(2),
            f32(2), f32(2), i32(2), key)
    assert program == "train"
    batch = {"tokens": i32(2, 16), "targets": i32(2, 16)}
    return jax.jit(jax.value_and_grad(
        lambda p, b: tr.loss_fn(p, b, cfg)[0])).lower(params, batch)


def _digest(name, program):
    with jax.default_matmul_precision("highest"):
        return hashlib.sha256(
            _lowered(name, program).as_text().encode()).hexdigest()


@pytest.mark.parametrize("name, program", sorted(PARENT_PROGRAMS))
def test_programs_that_are_no_step_lower_to_the_parents(name, program):
    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"digests were taken with jax {LOWERED_WITH_JAX}")
    assert _digest(name, program) == PARENT_PROGRAMS[name, program]


@pytest.mark.parametrize("name", sorted(DECODE_PROGRAMS))
def test_the_expert_families_decode_programs_are_this_trees(name):
    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"digests were taken with jax {LOWERED_WITH_JAX}")
    assert _digest(name, "decode") == DECODE_PROGRAMS[name]


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_a_steps_kernel_is_handed_the_segments_stacks_whole(kernel):
    """The weights are read where they lie: every `moe_step` call of a
    decode program takes the three stacks [repeats, E, D, F] of its
    segment and the layer as a scalar, never ONE layer's experts, which
    behind a layer scan is a copy of them every step; a chunk's scan still
    slices its layer for the form that runs every expert."""
    cfg = _wide("tiny-lfm2")
    E, F_ = cfg.num_experts, cfg.expert_ff
    params = jax.eval_shape(lambda k: stack.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    pool = jax.ShapeDtypeStruct(
        stack.pool_shape(cfg.count("attn"), 16, PAGE, cfg.kv_heads, cfg.hdim),
        jnp.float32)
    state = jax.eval_shape(lambda: stack.new_engine_state(
        cfg, 8, PAGE, jnp.float32, jnp.float32))

    def step(params, pool, state, tokens, at, tables):
        return stack.run_paged(params, tokens[:, None], cfg,
                               stack.Decode(cfg, at, tables, PAGE),
                               (pool, pool), state)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    jaxpr = jax.make_jaxpr(step)(params, pool, state, i32(8), i32(8),
                                 i32(8, 4))
    kernels = [e for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call" and any(
                   v.aval.shape[1:] == (E, 128, F_) for v in e.invars)]
    # four expert layers a period of the scanned segment
    assert len(kernels) >= 4
    for e in kernels:
        shapes = [v.aval.shape for v in e.invars]
        assert shapes.count((2, E, 128, F_)) == 2  # w_in, w_gate
        assert shapes.count((2, E, F_, 128)) == 1  # w_out
        assert (E, 128, F_) not in shapes


# -- through the engine ------------------------------------------------------


def _wide(name):
    """A tiny expert model at widths the kernel's gate takes."""
    base = get_config(name)
    if base.is_stack:
        return dataclasses.replace(base, d_model=128, d_ff_expert=256)
    return dataclasses.replace(base, d_model=128, d_ff=256)


def _serve(cfg, params, prompts, budgets):
    eng = InferenceEngine(params, cfg, EngineConfig(
        max_batch_size=8, page_size=PAGE, max_pages=65, max_seq_len=64,
        prefill_buckets=(8, 16), prefill_chunk=16, decode_span=4,
        busy_span=2, cache_dtype="float32"))
    try:
        reqs = [Request(request_id=f"r{i}", prompt=p, max_tokens=m)
                for i, (p, m) in enumerate(zip(prompts, budgets))]
        for r in reqs:
            eng.add_request(r)
        for r in reqs:
            assert r.done.wait(600) and r.error is None, r.error
    finally:
        eng.stop()
    return [r.output for r in reqs]


@pytest.mark.parametrize("name", ["tiny-moe", "tiny-lfm2"])
def test_overlapping_answers_are_the_xla_forms_and_the_experts_are_counted(
        name, monkeypatch):
    cfg = _wide(name)
    init = stack.init_params if cfg.is_stack else init_params
    params = init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(1, cfg.vocab_size, n)))
               for n in (5, 21, 9)]
    budgets = [6, 14, 10]
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "0")
    want = _serve(cfg, params, prompts, budgets)
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")
    before = common.counters()
    got = _serve(cfg, params, prompts, budgets)
    after = common.counters()
    assert got == want

    def delta(counter, **labels):
        return common.counter_delta(before, after, counter, **labels)

    held = delta("serve_moe_expert_steps", state="held")
    touched = delta("serve_moe_expert_steps", state="touched")
    layers = cfg.second_halves.count("moe")
    # every step of every span read back holds every layer's experts ...
    assert held > 0 and held % (layers * cfg.num_experts) == 0
    steps = held // (layers * cfg.num_experts)
    # ... and visited at least one live row's k of them in every layer, at
    # most three rows': touched + skipped = held, none negative
    assert (steps * layers * cfg.num_selected_experts <= touched
            <= min(held, 3 * steps * layers * cfg.num_selected_experts))
    assert 0 < held - touched < held
    # a step's rows computed are the visited experts' over its 8 rows
    assert delta("serve_moe_rows_computed") >= 8 * touched
