"""The expert products that read the experts where they lie (ops/moe.py),
against the form that runs every expert over every row
(`_moe_ffn_dropless_ids`). A decode step (`expert_step`,
models/transformer.py `moe_ffn_step`): the experts that a live row chose and
no others. A bucket or a prefill chunk (`expert_groups`, `moe_ffn_groups`):
each expert over the rows that chose it and no others. Which programs take
which (a step that knows its live rows, a `Seq` that keeps its keys) and
which keep the program they had; what the engine counts."""

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

from engine_programs import LOWERED_WITH_JAX, PINNED, digest

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


# -- a program of many tokens: each expert over the rows that chose it -------

# rows of the program, and how many hold a token (the first ones)
HELD = {"chunk-of-256-holding-1": ((1, 256), [1]),
        "chunk-of-256-holding-96": ((1, 256), [96]),
        "chunk-of-256-holding-256": ((1, 256), [256]),
        "bucket-of-2x16-short-of-its-rows": ((2, 16), [5, 16])}


def _rows(cfg, shape, seed=0):
    """`_layer`'s leaves with x [B,T,D] rows in place of a step's."""
    _, _, step_lp, whole_lp = _layer(cfg, seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 11), 2)
    return (jax.random.normal(ks[0], (*shape, D), jnp.float32),
            jax.random.normal(ks[1], (*shape, D), jnp.float32),
            step_lp, whole_lp)


@pytest.mark.parametrize("held", list(HELD))
@pytest.mark.parametrize("name", FORMULATIONS)
def test_the_groups_are_the_dropless_sum_over_the_rows_that_hold_a_token(
        name, held, kernel):
    cfg, handed = _formulation(name)
    shape, n_valid = HELD[held]
    x, other, step_lp, whole_lp = _rows(cfg, shape)
    gate = tr._moe_gate(other, whole_lp, cfg) if handed else None
    mask = np.arange(shape[1])[None, :] < np.asarray(n_valid)[:, None]
    want, _, want_ids = tr._moe_ffn_dropless_ids(x, whole_lp, cfg, gate)
    run = jax.jit(lambda x, lp, m: tr.moe_ffn_groups(x, lp, cfg, gate, m))
    got, ids = run(x, step_lp, jnp.asarray(mask))
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(want)[mask],
                               atol=TOL, rtol=0)
    if handed:
        return  # the choice was made from `other`: x's padding is free
    # a row of padding joins no group: whatever it holds, and whatever it
    # would have chosen, no real row's output moves by a bit
    noise = jnp.where(mask[..., None], x, 7.0 * x + 3.0)
    again, _ = run(noise, step_lp, jnp.asarray(mask))
    np.testing.assert_array_equal(np.asarray(again)[mask],
                                  np.asarray(got)[mask])


def _members(case, N, E):
    rng = np.random.default_rng(5)
    member = rng.random((N, E)) < 0.3
    if case == "an-expert-nobody-chose":
        member[:, [1, 6]] = False
    elif case == "every-row-chose-one-expert":  # N rows: more than one pass
        member[:] = False
        member[:, 3] = True
    elif case == "no-row-holds-a-token":
        member[:] = False
    elif case == "one-row":
        member[:] = False
        member[7, [0, 5]] = True
    return member


@pytest.mark.parametrize("case", [
    "ragged", "an-expert-nobody-chose", "every-row-chose-one-expert",
    "no-row-holds-a-token", "one-row"])
@pytest.mark.parametrize("N", [16, 144, 256])
def test_the_groups_kernel_is_the_xla_form_over_the_members(case, N, kernel):
    """`expert_groups` alone: the kernel against its XLA form (every expert
    over every row, times the combine's zeros), every row; rows that are
    no expert's member read zero. 144 rows: a pass of 128 and a ragged one;
    256 rows on one expert: two full passes."""
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    E = 8
    w_in, w_gate = (jax.random.normal(k, (LAYERS, E, D, F)) * 0.1
                    for k in ks[:2])
    w_out = jax.random.normal(ks[2], (LAYERS, E, F, D)) * 0.1
    x = jax.random.normal(ks[3], (N, D))
    c = jax.random.uniform(ks[4], (N, E))
    member = _members(case, N, E)
    args = (x, c, jnp.asarray(member), w_in, w_gate, w_out, LAYER)
    got = jax.jit(lambda *a: moe.expert_groups(*a, jax.nn.silu))(*args)
    want = moe.expert_groups(*args, jax.nn.silu, force_xla=True)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert not np.asarray(got)[~member.any(axis=1)].any()
    # the engine's count is a bound on what the passes cover
    rows = min(128, N)
    covered = int((-(-member.sum(axis=0) // rows) * rows).sum())
    tokens, k = int(member.any(axis=1).sum()), int(member.sum(axis=1).max())
    assert covered <= moe.groups_rows_bound(N, E, max(k, 1), tokens)


@pytest.mark.parametrize("E, k, N", [(64, 6, 256), (8, 2, 256), (16, 12, 256),
                                     (32, 4, 64), (64, 6, 16), (8, 2, 512)])
def test_the_rows_counted_for_a_grouped_program_are_never_fewer(E, k, N):
    """`groups_rows_bound` against the passes of drawn groups, from one
    token held to all, every token choosing k experts or (a share layer)
    up to k: the host's count never falls short of the kernel's rows, and
    never passes every expert over every pass."""
    rng = np.random.default_rng(E + k + N)
    rows = min(128, N)
    for tokens in (1, 2, N // 3, N - 1, N):
        for skew in (1.0, 8.0):
            p = rng.random(E) ** skew
            picks = [rng.choice(E, size=rng.integers(1, k + 1) if E == 16
                                else k, replace=False, p=p / p.sum())
                     for _ in range(tokens)]
            held = np.bincount(np.concatenate(picks), minlength=E)
            covered = int((-(-held // rows) * rows).sum())
            bound = moe.groups_rows_bound(N, E, k, tokens)
            assert covered <= bound <= E * -(-N // rows) * rows


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


def test_only_a_seq_that_keeps_its_keys_groups():
    """The rule of the grouped form is the static shape a mode sees and the
    mesh: a `Seq` that keeps its keys (a bucket, a chunk) knows the rows
    that hold a token; the plain forward (which may be differentiated), a
    step, `Verify`, a sharded mesh, a model without experts and a program
    whose rows do not fit the kernel's fast memory keep their forms."""
    cfg = get_config("tiny-moe")
    tables = jnp.ones((2, 4), jnp.int32)
    at = jnp.zeros((2,), jnp.int32)
    assert stack.Seq(cfg).kept_rows(2, 8) is None
    assert stack.Decode(cfg, at, tables, 4).kept_rows(2, 1) is None
    assert stack.Verify(cfg, at, tables, 4, at).kept_rows(2, 4) is None
    bucket = stack.Seq(cfg, n_valid=jnp.asarray([3, 8]), keep=True)
    np.testing.assert_array_equal(
        bucket.kept_rows(2, 8), np.arange(8)[None] < np.array([[3], [8]]))
    chunk = stack.Seq(cfg, n_valid=jnp.asarray([5]), keep=True,
                      chunk=(jnp.int32(16), tables[0]), page_size=4)
    assert np.asarray(chunk.kept_rows(1, 16)).sum() == 5
    assert np.asarray(stack.Seq(cfg, keep=True).kept_rows(2, 8)).all()
    # tiny-moe drops rows (capacity factor 1.25 of 4 top 2): with room for
    # every choice, as the cells' configurations have, nothing is dispatched
    roomy = dataclasses.replace(cfg, capacity_factor=2.0)
    assert tr.moe_seq_groups(roomy, 1, 256, None)
    assert tr.moe_seq_groups(get_config("tiny-lfm2"), 2, 16, None)
    assert not tr.moe_seq_groups(get_config("tiny-llama"), 1, 256, None)
    assert not tr.moe_seq_groups(get_config("tiny-sambay"), 1, 256, None)

    class Sharded:
        shape = {"tp": 2}

    assert not tr.moe_seq_groups(roomy, 1, 256, Sharded())
    # under a capacity rows can be dropped: the forms that dispatch
    assert not tr.moe_seq_groups(cfg, 1, 256, None)
    # the published shapes: 256 and 512 rows lie whole in VMEM, 4096 do not
    wide = dataclasses.replace(cfg, d_model=2560, d_ff=768, num_experts=64,
                               num_selected_experts=6, capacity_factor=64 / 6,
                               dtype="bfloat16")
    assert tr.moe_seq_groups(wide, 1, 256, None)
    assert tr.moe_seq_groups(wide, 1, 512, None)
    assert not tr.moe_seq_groups(wide, 1, 4096, None)
    # what the engine counts for such a program is the bound of its passes
    assert tr.moe_rows_computed(wide, 1, 256, tokens=256) == \
        moe.groups_rows_bound(256, 64, 6, 256) == 6 * 256 + 64 * 127
    assert tr.moe_rows_computed(wide, 1, 256, tokens=2) == 12 * 128
    assert tr.moe_rows_computed(wide, 1, 256) == 64 * 256
    assert tr.moe_rows_computed(wide, 1, 4096, tokens=4096) == 64 * 4096


# sha256 of the StableHLO text of the programs that must NOT change, as this
# tree's parent (c4ad2b2) lowers them for the CPU at `highest` matmul
# precision: `Verify` (tests/engine_programs.py: PINNED, and whose text each
# engine program is) and a training step with and without experts, taken with
# jax LOWERED_WITH_JAX. tiny-llama's train step is PR 47's own: the training
# layer names the FFN's two products for the loop's checkpoint; outside one
# (`tiny-llama` keeps everything) a name lowers to nothing, but each moves the
# numbers in the text's private function names, and nothing else
# (tests/test_remat_policy.py)
TRAIN_STEPS = {
    "tiny-moe":
        "3b3c9924d4d7ee999a31808dd0bcf82b8dfc8eaed4b85c3633356b85726fe7a5",
    "tiny-llama":
        "2fc8405287500755aa3cb12c89290afebca18e2e0ea99381c889fe7f6f039f1a",
}
# the expert families' decode programs DID change (a step visits): the newest
# family's is pinned too, so that a later change to it is one that is meant;
# so did the chunk and bucket programs of the families whose experts drop
# nothing (PR 43: each expert over the rows that chose it; at the tiny width
# the op's XLA form, every expert times the combine's zeros, with the layer
# read out of the segment's stacks)
GROUPED_PROGRAMS = [(name, program)
                    for name in ("tiny-lfm2", "tiny-longcat-flash",
                                 "tiny-smallthinker")
                    for program in ("bucket", "chunk")]
PAGE = 4


@pytest.mark.parametrize("name, program", [
    ("tiny-llama", "train"), ("tiny-llama", "verify"),
    ("tiny-moe", "train"), ("tiny-moe", "verify")])
def test_programs_that_are_no_step_lower_to_the_parents(name, program):
    if program == "verify":
        assert digest(name, program) == PINNED[name, program]
        return
    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"digests were taken with jax {LOWERED_WITH_JAX}")
    cfg = get_config(name)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("tokens", "targets")}
    with jax.default_matmul_precision("highest"):
        text = jax.jit(jax.value_and_grad(
            lambda p, b: tr.loss_fn(p, b, cfg)[0])).lower(
                params, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == TRAIN_STEPS[name]


def test_the_expert_families_decode_programs_are_this_trees():
    assert digest("tiny-smallthinker", "decode") == PINNED[
        "tiny-smallthinker", "decode"]


@pytest.mark.parametrize("name, program", GROUPED_PROGRAMS)
def test_the_expert_families_chunk_and_bucket_programs_are_this_trees(
        name, program):
    assert digest(name, program) == PINNED[name, program]


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_the_step_kernels_operations_are_the_parents_in_their_order(kernel):
    """The profiler's fingerprint of a decode program covers a Pallas
    kernel's operations in their order, not where they are written (PERF.md
    section 6, PR 43): `moe_step`'s body and its blocks' index maps, as
    jaxprs, are those of PR 42's kernel (the parent of PR 43, c4ad2b2 ..
    8a0fcb9), whatever helpers the body shares with `moe_groups`."""
    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"the digest was taken with jax {LOWERED_WITH_JAX}")
    S = jax.ShapeDtypeStruct
    E = 8
    jaxpr = jax.make_jaxpr(
        lambda x, c, hit, a, b, d: moe.expert_step(
            x, c, hit, a, b, d, LAYER, jax.nn.silu)[0])(
        S((ROWS, D), jnp.float32), S((ROWS, E), jnp.float32), S((E,), bool),
        S((LAYERS, E, D, F), jnp.float32), S((LAYERS, E, D, F), jnp.float32),
        S((LAYERS, E, F, D), jnp.float32))
    call = next(e for e in _eqns(jaxpr.jaxpr)
                if e.primitive.name == "pallas_call")
    assert call.params["name"] == "moe_step"
    text = str(call.params["jaxpr"]) + "".join(
        str(b.index_map_jaxpr)
        for b in call.params["grid_mapping"].block_mappings)
    # taken from the parent's tree at `highest` matmul precision, as
    # tests/conftest.py sets it
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "eecb394c47a954af3b88f18f827e716a63fa71785773ad4ef219efbe73794d7e")


@pytest.mark.parametrize("program", ["decode", "chunk", "bucket"])
def test_a_kernel_is_handed_the_segments_stacks_whole(program, kernel):
    """The weights are read where they lie: every `moe_step` call of a
    decode program, and every `moe_groups` call of a chunk or a bucket,
    takes the three stacks [repeats, E, D, F] of its segment and the layer
    as a scalar, never ONE layer's experts, which behind a layer scan is a
    copy of them every call."""
    cfg = _wide("tiny-lfm2")
    E, F_ = cfg.num_experts, cfg.expert_ff
    params = jax.eval_shape(lambda k: stack.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    pool = jax.ShapeDtypeStruct(
        stack.pool_shape(cfg.count("attn"), 16, PAGE, cfg.kv_heads, cfg.hdim),
        jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731

    if program == "decode":
        state = jax.eval_shape(lambda: stack.new_engine_state(
            cfg, 8, PAGE, jnp.float32, jnp.float32))

        def step(params, pool, state, tokens, at, tables):
            return stack.run_paged(params, tokens[:, None], cfg,
                                   stack.Decode(cfg, at, tables, PAGE),
                                   (pool, pool), state)

        jaxpr = jax.make_jaxpr(step)(params, pool, state, i32(8), i32(8),
                                     i32(8, 4))
    elif program == "chunk":
        state = jax.eval_shape(
            lambda: stack.new_request_state(cfg, 1, jnp.float32))

        def chunk(params, pool, state, tokens, start, table, last):
            return stack.run_paged(
                params, tokens[None], cfg,
                stack.Seq(cfg, n_valid=(last + 1)[None], keep=True,
                          chunk=(start, table), page_size=PAGE),
                (pool, pool), state)

        jaxpr = jax.make_jaxpr(chunk)(params, pool, state, i32(16), i32(),
                                      i32(8), i32())
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, t, n: stack.prefill(p, cfg, t, n))(
                params, i32(2, 16), i32(2))
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
    names = {e.params["name"] for e in kernels}
    assert names == {"moe_step" if program == "decode" else "moe_groups"}


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
