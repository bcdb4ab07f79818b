"""The trained expert layer (models/transformer.py `_moe_ffn_grouped`,
ops/moe.py `group_rows` / `grouped_ffn`): the tokens' choices sorted by
expert, each held expert over its own rows and no others, against the form
that runs every expert over every row (`_moe_ffn_dropless_ids`), forward and
gradients, under even and piled-up routing and at the bound; the three
kernels in interpret mode against their XLA forms; the rule that picks the
form; the buffer's bound, which a routing past it fails on and never drops."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_config, init_params
from ray_tpu.models import transformer as tr
from ray_tpu.ops import moe

TOL = 2e-5


@pytest.fixture
def kernel(monkeypatch):
    """The Pallas kernels in interpret mode, as the other kernels' tests."""
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


def _formulation(name):
    """-> a configuration of width 128 whose rows (B x T = 512) give the
    grouped form by the rule."""
    if name == "8-top-2-swiglu-softmax":  # Mixtral's layer: every expert held
        return dataclasses.replace(
            get_config("tiny-moe"), d_model=128, d_ff=128, num_experts=8,
            num_selected_experts=2, capacity_factor=4.0, dtype="float32",
            max_seq_len=512)
    if name == "8-of-8-sigmoid-bias-shared":
        return dataclasses.replace(get_config("tiny-trinity"))
    assert name == "4-held-of-16-sigmoid-bias"  # a share layer
    return dataclasses.replace(
        get_config("tiny-trinity"), num_experts=4, n_routed_experts=16,
        experts_first=4, num_selected_experts=4, capacity_factor=1.0)


FORMULATIONS = ["8-top-2-swiglu-softmax", "8-of-8-sigmoid-bias-shared",
                "4-held-of-16-sigmoid-bias"]


def _layer(cfg, seed=0, pile=0.0):
    """One expert layer's leaves and its rows; `pile`: the rows share a
    direction that the router's first three outputs score by so much, which
    piles the choices up on a few experts."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    D, F, E, W = cfg.d_model, cfg.expert_ff, cfg.num_experts, cfg.router_width
    shared = jnp.ones((D,)) / D ** 0.5
    lp = {"router": jax.random.normal(ks[0], (D, W)) * 0.1
          + pile * shared[:, None] * (jnp.arange(W) < 3),
          "w_in": jax.random.normal(ks[1], (E, D, F)) * 0.05,
          "w_gate": jax.random.normal(ks[2], (E, D, F)) * 0.05,
          "w_out": jax.random.normal(ks[3], (E, F, D)) * 0.05,
          "router_bias": jax.random.normal(ks[4], (W,)) * 0.01}
    return lp, jax.random.normal(ks[5], (2, 256, D)) + 2.0 * shared


@pytest.mark.parametrize("pile", [0.0, 1.0], ids=["even", "piled-up"])
@pytest.mark.parametrize("name", FORMULATIONS)
def test_the_grouped_layer_is_the_dropless_sum_and_so_are_its_gradients(
        name, pile):
    cfg = _formulation(name)
    lp, x = _layer(cfg, pile=pile)
    grouped = tr.moe_grouped(cfg, 2, 256, None)
    assert grouped is not None

    def loss(form):
        def f(x, lp):
            out, _, ids = form(x, lp)
            return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(
                out.shape) * 0.01)), (out, ids)
        return f

    new = loss(lambda x, lp: tr._moe_ffn_grouped(x, lp, cfg, None, *grouped))
    old = loss(lambda x, lp: tr._moe_ffn_dropless_ids(x, lp, cfg))
    with jax.default_matmul_precision("highest"):
        (_, (got, ids)), g_new = jax.value_and_grad(new, (0, 1), has_aux=True)(x, lp)
        (_, (want, ids_old)), g_old = jax.value_and_grad(old, (0, 1), has_aux=True)(x, lp)
    assert (ids == ids_old).all()
    counts = np.bincount(np.asarray(ids).ravel(), minlength=cfg.router_width)
    if pile:  # the fullest expert holds well over the even share
        assert counts.max() > 1.5 * counts.mean()
    np.testing.assert_allclose(got, want, atol=TOL)
    flat_new, flat_old = jax.tree.leaves(g_new), jax.tree.leaves(g_old)
    for a, b in zip(flat_new, flat_old):
        np.testing.assert_allclose(a, b, atol=TOL * max(1.0, float(jnp.abs(b).max())))
    # what only this backward reaches is not zero: the weights' gradient of
    # every held expert that was chosen, and the router's through the combine
    assert float(jnp.abs(g_new[1]["w_in"]).max()) > 1e-3
    assert float(jnp.abs(g_new[1]["router"]).max()) > 1e-4


@pytest.mark.parametrize("transpose", [False, True], ids=["rows", "transposed"])
def test_the_product_kernel_is_its_xla_form(transpose, kernel):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    E, K, N, tile = 4, 128, 256, 128
    x = jax.random.normal(ks[0], (6 * tile, K))
    w = jax.random.normal(ks[1], (E, N, K) if transpose else (E, K, N))
    tile_expert = jnp.asarray([0, 0, 1, 3, 3, 3], jnp.int32)
    for used in (6, 4, 1):
        args = (x, w, tile_expert, jnp.asarray([used], jnp.int32))
        got = moe._gmm_pallas(*args, tile=tile, transpose=transpose, name="t")
        want = moe._gmm_xla(*args, tile=tile, transpose=transpose, name="t")
        np.testing.assert_allclose(got, want, atol=1e-4)
        assert not np.asarray(got[used * tile:]).any()  # unused tiles: zeros
        assert np.asarray(got[:used * tile]).any()


def test_the_weights_kernel_is_its_xla_form_and_writes_every_expert(kernel):
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    E, K, N, tile = 4, 128, 256, 128
    x = jax.random.normal(ks[0], (6 * tile, K))
    dy = jax.random.normal(ks[1], (6 * tile, N))
    # expert 2's one tile holds rows of zeros (nobody chose it): its
    # gradient is written, as zeros
    x = x.at[3 * tile:4 * tile].set(0.0)
    tile_expert = jnp.asarray([0, 0, 1, 2, 3, 3], jnp.int32)
    for used in (6, 5):
        args = (x, dy, tile_expert.at[used:].set(3), jnp.asarray([used], jnp.int32))
        kw = dict(tile=tile, experts=E, dtype=jnp.float32, name="t")
        got = moe._tgmm_pallas(*args, **kw)
        want = moe._tgmm_xla(*args, **kw)
        np.testing.assert_allclose(got, want, atol=2e-4)
        assert not np.asarray(got[2]).any() and np.asarray(got[3]).any()
    want = jnp.einsum("rk,rn->kn", x[:2 * tile], dy[:2 * tile])
    np.testing.assert_allclose(got[0], want, atol=2e-4)


def test_the_sorted_buffer_holds_every_held_choice_once_in_its_experts_tiles():
    N, k, W, first, E, tile = 64, 3, 16, 4, 4, 8
    ids = jnp.stack([jax.random.permutation(jax.random.PRNGKey(i), W)[:k]
                     for i in range(N)]).astype(jnp.int32)
    weights = jax.random.uniform(jax.random.PRNGKey(9), (N, k))
    bound = moe.grouped_rows_bound(N, k, E, W, tile)
    rows = moe.group_rows(ids, weights, first, E, tile, bound)
    token, weight = np.asarray(rows["token"]), np.asarray(rows["weight"])
    tile_expert, used = np.asarray(rows["tile_expert"]), int(rows["used"][0])
    held = (np.asarray(ids) >= first) & (np.asarray(ids) < first + E)
    assert (token < N).sum() == held.sum() <= bound
    assert int(rows["rows"]) == used * tile <= bound
    seen = set()
    for r in np.nonzero(token < N)[0]:
        e = first + tile_expert[r // tile]
        j = int(np.nonzero(np.asarray(ids)[token[r]] == e)[0][0])  # it chose e
        assert weight[r] == np.asarray(weights)[token[r], j]
        seen.add((int(token[r]), int(e)))
    assert len(seen) == held.sum()  # each held choice once
    assert (weight[token == N] == 0).all()
    assert (np.diff(tile_expert) >= 0).all() and set(tile_expert[:used]) == set(range(E))
    assert (tile_expert[used:] == E - 1).all()


def test_the_bound_is_exact_where_every_expert_is_held_and_twice_the_share_else():
    # every expert held: every choice and a tile an expert; nothing can pass it
    assert moe.grouped_rows_bound(8192, 2, 8, 8, 256) == 8192 * 2 + 8 * 256
    # a share: twice the even share of the choices and a tile an expert
    assert moe.grouped_rows_bound(32768, 8, 16, 128, 512) == 2 * 32768 + 16 * 512
    # never more than every token choosing min(k, E) held experts
    assert moe.grouped_rows_bound(64, 8, 2, 4, 8) == 64 * 2 + 2 * 8
    assert moe.grouped_tile(2048) == 512 and moe.grouped_tile(512) == 256
    assert moe.grouped_tile(128) == 128


def test_a_routing_past_the_bound_fails_loudly_and_drops_nothing():
    """Every choice piled on the 4 held experts of 16: twice the even share
    is passed, the layer's output is NaN (not a sum with rows left out), and
    the step's metrics raise where the host publishes them."""
    from ray_tpu.util import profiler

    cfg = _formulation("4-held-of-16-sigmoid-bias")
    lp, x = _layer(cfg)
    lp["router_bias"] = jnp.where((jnp.arange(16) >= 4) & (jnp.arange(16) < 8),
                                  5.0, 0.0)
    tile, bound = tr.moe_grouped(cfg, 2, 256, None)
    out, _, ids = tr._moe_ffn_grouped(x, lp, cfg, None, tile, bound)
    assert ((ids >= 4) & (ids < 8)).all() and ids.size > bound
    assert np.isnan(np.asarray(out)).all()
    before = profiler._c_moe["train_moe_rows_overflow"].get()
    step = {"moe_choices_held": ids.size, "moe_rows_max": 512,
            "moe_rows_bound": bound, "moe_bias_moved": 0}
    with pytest.raises(RuntimeError, match="Nothing is dropped"):
        profiler.publish_moe_step(
            {**step, "moe_rows_short": ids.size + 4 * tile - bound})
    assert profiler._c_moe["train_moe_rows_overflow"].get() == before + 1
    profiler.publish_moe_step({**step, "moe_rows_short": 0})  # at the bound
    profiler.publish_moe_step({"loss": 1.0})  # another model's: left alone


def test_the_rule_is_the_static_shape_and_the_mesh():
    """Rows in their hundreds an expert, whole lane tiles, no identity
    experts, no sharded mesh: Mixtral's training rows take the grouped form
    by the same rule; a `Verify` of a few rows, the tiny widths and a model
    without experts keep what they had."""
    # the benchmark's Mixtral is dropless (capacity_factor = experts / k)
    mixtral = get_config("mixtral-8x7b", capacity_factor=4.0)
    assert tr.moe_grouped(mixtral, 1, 8192, None) == (512, 8192 * 2 + 8 * 512)
    assert tr.moe_grouped(get_config("mixtral-8x7b"), 1, 8192, None) is None
    wide = dataclasses.replace(get_config("tiny-moe"), d_model=256, d_ff=512,
                               num_experts=8, num_selected_experts=2,
                               capacity_factor=4.0)
    assert tr.moe_grouped(wide, 1, 8192, None) == (512, 8192 * 2 + 8 * 512)
    assert tr.moe_grouped(wide, 4, 4, None) is None          # a few rows
    assert tr.moe_grouped(get_config("tiny-moe"), 1, 8192, None) is None  # drops
    assert tr.moe_grouped(get_config("tiny-lfm2"), 1, 8192, None) is None  # 64 wide
    assert tr.moe_grouped(get_config("tiny-llama"), 1, 8192, None) is None
    longcat = dataclasses.replace(get_config("tiny-longcat-flash"), d_model=128,
                                  d_ff_expert=128)
    assert tr.moe_grouped(longcat, 1, 8192, None) is None    # identity experts

    class mesh:
        shape = {"fsdp": 2}

    assert tr.moe_grouped(wide, 1, 8192, mesh) is None


def test_mixtrals_training_rows_take_the_grouped_form_with_unchanged_numbers(
        monkeypatch):
    """`loss_fn` of a Mixtral-shaped model (one-block, every expert held,
    dropless) over 512 tokens: the rule gives the grouped form, and the loss
    and every gradient leaf are the old form's."""
    cfg = _formulation("8-top-2-swiglu-softmax")
    cfg = dataclasses.replace(cfg, n_layers=2, remat=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 257), 0, cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    assert tr.moe_grouped(cfg, 2, 256, None) is not None
    grad = jax.jit(jax.value_and_grad(lambda p: tr.loss_fn(p, batch, cfg)[0]))
    with jax.default_matmul_precision("highest"):
        loss_new, g_new = grad(params)
        monkeypatch.setattr(tr, "moe_grouped", lambda *a, **k: None)
        loss_old, g_old = jax.jit(jax.value_and_grad(
            lambda p: tr.loss_fn(p, batch, cfg)[0]))(params)
    assert abs(float(loss_new) - float(loss_old)) < 1e-6
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_allclose(a, b, atol=2e-6)


def _routing(case, N=64):
    """-> (ids [N,k], weights, first, E, W, tile, bound) of a share layer,
    of a layer that holds every expert, and of a share layer whose routing
    passes the bound (every token chooses held experts alone)."""
    k, W, tile = 3, 16, 8
    first, E = (0, W) if case == "all-held" else (4, 4)
    pick = jnp.arange(first, first + E) if case == "past-the-bound" else (
        jnp.arange(W))
    ids = jnp.stack([jax.random.permutation(jax.random.PRNGKey(i), pick)[:k]
                     for i in range(N)]).astype(jnp.int32)
    weights = jax.random.uniform(jax.random.PRNGKey(9), (N, k))
    return (ids, weights, first, E, W, tile,
            moe.grouped_rows_bound(N, k, E, W, tile))


@pytest.mark.parametrize("case", ["share", "all-held", "past-the-bound"])
def test_slot_is_the_inverse_of_token(case):
    ids, weights, first, E, W, tile, bound = _routing(case)
    N, k = ids.shape
    rows = moe.group_rows(ids, weights, first, E, tile, bound)
    token, slot = np.asarray(rows["token"]), np.asarray(rows["slot"])
    tile_expert = np.asarray(rows["tile_expert"])
    held = (np.asarray(ids) >= first) & (np.asarray(ids) < first + E)
    named = slot < bound
    assert slot.shape == (N, k) and (slot[~named] == bound).all()
    assert not named[~held].any()  # a choice that is not held: the fill row
    past = int(rows["rows"]) > bound
    assert past == (case == "past-the-bound")
    # every held choice has its row, but for those a routing past the bound
    # leaves out; every used row is named exactly once
    assert past or (named == held).all()
    n, j = np.nonzero(named)
    assert (token[slot[n, j]] == n).all()
    assert (first + tile_expert[slot[n, j] // tile] == np.asarray(ids)[n, j]).all()
    assert sorted(slot[named]) == sorted(np.nonzero(token < N)[0])
    # `choice`: the same rows by their choice's number; no two rows name one
    choice = np.asarray(rows["choice"])
    assert (choice[slot[n, j]] == n * k + j).all()
    assert len(set(choice)) == bound and (choice[token == N] >= N * k).all()
    # the runs' table: a tile of tokens' rows of one expert lie side by side
    runs = np.asarray(rows["runs"])
    assert runs.shape == (-(-N // moe._COMBINE_TOKENS) + 1, E)
    for e in range(E):
        mine = np.sort(slot[named & (np.asarray(ids) == first + e)])
        assert not len(mine) or (runs[0, e] == mine[0] and (
            past or runs[-1, e] == mine[-1] + 1))


def _scatter_add_layer(x, lp, cfg, tile, bound):
    """The form `_moe_ffn_grouped` had: XLA's gather into the buffer (whose
    transpose scatter-adds) and a float32 scatter-add back."""
    dtype = x.dtype
    B, T, D = x.shape
    N, E, k = B * T, cfg.num_experts, cfg.num_selected_experts
    _, weights, expert_ids = tr._moe_gate(x, lp, cfg)
    rows = moe.group_rows(expert_ids.reshape(N, k), weights.reshape(N, k),
                          cfg.experts_first, E, tile, bound)
    sorted_x = jnp.take(x.reshape(N, D), rows["token"], axis=0, mode="fill",
                        fill_value=0)
    y = moe.grouped_ffn(tr._GATE_ACT[cfg.activation], tile, sorted_x,
                        lp["w_in"], lp["w_gate"], lp["w_out"],
                        rows["tile_expert"], rows["used"])
    out = jnp.zeros((N, D), jnp.float32).at[rows["token"]].add(
        y.astype(jnp.float32) * rows["weight"][:, None], mode="drop")
    return out.astype(dtype).reshape(B, T, D)


def _layer_loss(form):
    def f(x, lp):
        out = form(x, lp)
        return jnp.sum(out * jnp.cos(
            jnp.arange(out.size).reshape(out.shape) * 0.01))
    return f


@pytest.mark.parametrize("name", ["8-top-2-swiglu-softmax",
                                  "4-held-of-16-sigmoid-bias"])
def test_the_gather_sum_is_the_scatter_add_it_replaces_and_so_are_its_gradients(
        name):
    cfg = _formulation(name)
    lp, x = _layer(cfg, pile=1.0)
    grouped = tr.moe_grouped(cfg, 2, 256, None)
    new = _layer_loss(lambda x, lp: tr._moe_ffn_grouped(
        x, lp, cfg, None, *grouped)[0])
    old = _layer_loss(lambda x, lp: _scatter_add_layer(x, lp, cfg, *grouped))
    with jax.default_matmul_precision("highest"):
        v_new, g_new = jax.value_and_grad(new, (0, 1))(x, lp)
        v_old, g_old = jax.value_and_grad(old, (0, 1))(x, lp)
    assert abs(float(v_new) - float(v_old)) < 1e-4 * abs(float(v_old))
    assert set(g_new[1]) >= {"router", "w_in", "w_gate", "w_out"}
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        # float32 sums in another order, no more
        np.testing.assert_allclose(a, b, atol=2e-6 * max(1.0, float(jnp.abs(b).max())))
    assert float(jnp.abs(g_new[1]["router"]).max()) > 1e-4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [True, False], ids=["weighted", "plain"])
@pytest.mark.parametrize("case", ["share", "all-held", "past-the-bound"])
def test_the_combine_kernel_is_its_xla_form(case, weighted, dtype, kernel):
    N = 3 * moe._COMBINE_TOKENS  # a tile's copies start under the one before
    ids, weights, first, E, W, _, _ = _routing(case, N=N)
    tile = 16
    bound = moe.grouped_rows_bound(N, ids.shape[1], E, W, tile)
    rows = moe.group_rows(ids, weights, first, E, tile, bound)
    y = jax.random.normal(jax.random.PRNGKey(3), (bound, 128)).astype(dtype)
    w = weights if weighted else jnp.ones_like(weights)
    args = (y, rows["slot"], w, rows["runs"])
    kw = dict(weighted=weighted, dtype=jnp.float32, name="moe_combine")
    got = moe._combine_pallas(*args, **kw)
    want = moe._combine_xla(*args, **kw)
    np.testing.assert_allclose(got, want, atol=2e-6)
    token = np.asarray(rows["token"])
    scattered = jnp.zeros((N, 128)).at[rows["token"]].add(
        y.astype(jnp.float32) * (rows["weight"] if weighted else (
            token < N))[:, None], mode="drop")
    np.testing.assert_allclose(got, scattered, atol=2e-6)
    # through the dispatch, as the layer calls it: rounded once to y's type
    got = moe.gather_rows(y, rows, weights if weighted else None)
    assert got.dtype == y.dtype
    np.testing.assert_allclose(got.astype(jnp.float32), want.astype(dtype),
                               atol=2e-6 if dtype == "float32" else 0.04)


@pytest.mark.parametrize("name", ["8-top-2-swiglu-softmax",
                                  "4-held-of-16-sigmoid-bias"])
def test_the_layer_and_its_gradient_scatter_add_no_rows(name):
    """Structural: the traced value-and-grad of the layer holds no
    scatter-add whose update is 2-D (rows); the parent's form, traced the
    same way, holds them, so the search finds what it looks for."""
    cfg = _formulation(name)
    lp, x = _layer(cfg)
    grouped = tr.moe_grouped(cfg, 2, 256, None)

    def row_scatters(form):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(_layer_loss(form), (0, 1)))(
            x, lp)
        found = []

        def walk(j):
            for eqn in j.eqns:
                # 2-D: rows of the stream's width (the XLA forms of the
                # weights' kernel and the router's top k add 3-D updates)
                if eqn.primitive.name.startswith("scatter") and (
                        eqn.invars[2].aval.ndim == 2):
                    found.append(eqn.primitive.name)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    assert not row_scatters(lambda x, lp: tr._moe_ffn_grouped(
        x, lp, cfg, None, *grouped)[0])
    assert len(row_scatters(
        lambda x, lp: _scatter_add_layer(x, lp, cfg, *grouped))) == 2


def test_the_layer_through_its_kernels_is_the_layer_through_their_xla_forms(
        monkeypatch):
    """The share layer's value and gradients with every kernel in interpret
    mode (the three products, and `moe_combine` forward and backward)
    against the XLA forms the CPU takes."""
    cfg = _formulation("4-held-of-16-sigmoid-bias")
    lp, x = _layer(cfg, pile=1.0)
    grouped = tr.moe_grouped(cfg, 2, 256, None)
    loss = _layer_loss(lambda x, lp: tr._moe_ffn_grouped(
        x, lp, cfg, None, *grouped)[0])
    with jax.default_matmul_precision("highest"):
        v_xla, g_xla = jax.value_and_grad(loss, (0, 1))(x, lp)
        monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")
        jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(x, lp)
        # out and dx, each as the TPU's branch and as the interpreted one
        assert str(jaxpr).count("name=moe_combine") == 4
        v_kernel, g_kernel = jax.value_and_grad(loss, (0, 1))(x, lp)
    assert abs(float(v_kernel) - float(v_xla)) < 1e-4 * abs(float(v_xla))
    for a, b in zip(jax.tree.leaves(g_kernel), jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(a, b, atol=TOL * max(1.0, float(jnp.abs(b).max())))
