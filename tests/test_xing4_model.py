"""The Xing4.0 stack TRAINED (the `xing4_0` family's tiny cut: latent
attention with a bottleneck on the queries and yarn rotary lanes, four
residual streams mixed round every sublayer, a leading dense layer, then a
share of sigmoid-routed experts beside a shared one, and a multi-token
prediction block in the loss) against the plain reference of its family
(benchmark/reference/xing4.py: float32, `highest`, no kernel, no sorting,
nothing imported from the program), on seeded weights: the logits, the main
head's and the prediction block's loss, every compared gradient block of
L = L_main + 0.1 L_mtp, per control mode; the residual path's invariants;
the yarn table; the share test; the router's bias under a train step;
`make_train_step` under `dp` against one device; the one-stream programs'
lowered text against the parent's; and the benchmark's own rehearsal.

Tolerances. Weights are the family's draws cast to float32 and the tiny
model runs in float32, so program and reference differ only in the order of
float32 sums (and in (v phi) / rms for (v / rms) phi): the limits are TOL on
a loss and GRAD_TOL on a gradient block relative to the block's norm. Every
control mode lands far outside."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common
from benchmark.reference import xing4 as ref
from benchmark.tests.tiny import tiny_spec
from engine_programs import LOWERED_WITH_JAX, digest
from ray_tpu.core.metrics import registry
from ray_tpu.models import forward, get_config, init_params, param_axes, stack
from ray_tpu.models import transformer as tr
from ray_tpu.ops.rope import yarn_inv_freq, yarn_mscale
from ray_tpu.train.lm import make_optimizer, make_train_step

TOL = 5e-5
GRAD_TOL = 2e-3
CONFIG = "xing4.0-29b-a4b"
CELL = CONFIG + ".train-packed-x2"
T = 512  # rows enough an expert for the grouped form (`moe_grouped`)


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec(CONFIG)
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(58)))
    cfg = family.model_config(spec, dtype="float32")
    toks = jax.random.randint(jax.random.PRNGKey(5), (T + 1,), 3,
                              spec["vocab_size"])
    return spec, family, cfg, params, toks[:-1], toks[1:]


@pytest.fixture(scope="module")
def compared(model):
    """(the program's nll and flat gradient, the reference's nll, the
    prediction block's nll and its gradient blocks)."""
    spec, family, cfg, params, tokens, targets = model
    with jax.default_matmul_precision("highest"):
        return (family.program_probe(cfg, params, tokens, targets),
                ref.losses_and_grads(params, tokens, targets, spec))


def _blocks(family, flat, shapes):
    """The flat compared gradient cut back into its named blocks."""
    out, at = {}, 0
    for name in family.GRAD_SCALES:
        size = int(np.prod(shapes[name]))
        out[name] = np.asarray(flat[at:at + size]).reshape(shapes[name])
        at += size
    assert at == flat.shape[0]
    return out


def test_the_cut_is_one_dense_layer_a_scan_of_four_and_the_block(model):
    spec, family, cfg, params, *_ = model
    assert cfg.segments() == ((0, ("mla",), 1), (1, ("mla",), 4))
    assert cfg.second_halves == ("ffn",) + ("moe",) * 4
    assert cfg.untrainable == "" and cfg.hc_streams == 4 and cfg.mtp_depth == 1
    assert cfg.counts_choices and cfg.router_width == 8 and cfg.num_experts == 4
    assert params["layers"][1][0]["hc1_phi"].shape == (4, 4, 128, 24)
    assert params["mtp"]["layer"]["router"].shape == (128, 8)
    assert params["mtp"]["proj"].shape == (256, 128)
    assert cfg.param_count() == sum(a.size for a in jax.tree.leaves(params))
    full = common.load_json("configs", CONFIG + ".json")
    assert family.model_config(full).rope_yarn == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)


def test_the_logits_and_both_losses_agree_with_the_plain_reference(
        model, compared):
    spec, family, cfg, params, tokens, targets = model
    (nll, _), (ref_nll, ref_after, _) = compared
    assert np.abs(np.asarray(nll) - np.asarray(ref_nll)).max() < TOL
    with jax.default_matmul_precision("highest"):
        logits, _, after = jax.jit(lambda p: forward(
            p, tokens[None], cfg, mtp_tokens=targets[None]))(params)
        want = ref.logits_at(params, tokens, jnp.arange(T), spec)
        loss, metrics = jax.jit(lambda p: tr.loss_fn(
            p, {"tokens": tokens[None], "targets": targets[None]}, cfg,
            z_loss_coef=0.0))(params)
    assert np.abs(np.asarray(logits[0]) - np.asarray(want)).max() < TOL
    lse = jax.scipy.special.logsumexp(after[0], axis=-1)
    after_nll = lse - jnp.take_along_axis(
        after[0], jnp.roll(targets, -1)[:, None], -1)[:, 0]
    assert np.abs(np.asarray(after_nll)[:-1]
                  - np.asarray(ref_after)[:-1]).max() < TOL
    assert float(ref_after[-1]) == 0.0  # the last position has no target
    mtp = float(jnp.sum(ref_after) / (T - 1))
    assert abs(float(metrics["mtp_loss"]) - mtp) < TOL
    assert abs(float(metrics["ce_loss"]) - float(jnp.mean(ref_nll))) < TOL
    assert abs(float(loss) - float(jnp.mean(ref_nll)) - 0.1 * mtp) < TOL
    assert abs(float(metrics["loss"]) - float(loss)) == 0.0


def test_every_gradient_block_of_the_whole_loss_agrees(model, compared):
    """Block by block (one test: the comparison is one forward and backward
    of the program and one of the reference, some 100 CPU-seconds)."""
    _, family, *_ = model
    (_, flat), (_, _, grads) = compared
    shapes = {name: grads[name].shape for name in family.GRAD_SCALES}
    got = _blocks(family, flat, shapes)
    assert list(got) == ["ln1", *(f"hc{i}_{part}" for part in ("phi", "b", "a")
                                  for i in (1, 2)),
                         "w_in", "w_gate", "w_out", "router", "router_bias",
                         "proj"]
    for block, scale in family.GRAD_SCALES.items():
        want = np.asarray(grads[block])
        if block == "router_bias":
            # the bias is in the choice only: nothing of L leans on it, in
            # the reference and in the program alike (where it enters the
            # weights, control mode bias-in-weight, this block is not zero:
            # test_the_bias_in_the_weights_shows_in_the_bias_gradient)
            assert want.shape == (8,)
            assert not want.any() and not got[block].any()
            continue
        assert np.linalg.norm(want) > 0, block
        if block.startswith("hc") or block == "ln1":
            assert want.shape[0] == 6  # five layers, then the block's row
            assert np.linalg.norm(want[-1]) > 0, block
        assert np.linalg.norm(got[block] / scale - want) \
            < GRAD_TOL * np.linalg.norm(want), block


@pytest.fixture(scope="module")
def small(model):
    """The program's side on a row of 128 (the dropless experts), for the
    control modes: each mode compiles the reference's pieces anew."""
    spec, family, cfg, params, tokens, targets = model
    with jax.default_matmul_precision("highest"):
        return tokens[:128], targets[:128], family.program_probe(
            cfg, params, tokens[:128], targets[:128])


@pytest.mark.parametrize("mode", ref.EQUATION_MODES + ref.PRECISION_MODES)
def test_the_program_is_held_apart_from_each_control_mode(model, small, mode):
    spec, family, cfg, params, *_ = model
    tokens, targets, (nll, flat) = small
    with jax.default_matmul_precision("highest"):
        mode_nll, mode_flat = family.nll_and_norm_grads(
            params, tokens, targets, spec, mode)
    numbers = checks.train_numbers(nll, flat, mode_nll, mode_flat)
    # how far outside the tolerances a mode lands (loss, gradient): 20 x,
    # but: without the prediction block the main head's loss is the SAME;
    # yarn hardly shows at random weights on a row of 128 (nearly even
    # softmaxes whatever the scores' scale: past the loss's tolerance, which
    # the program agrees a hundred times inside; 0.16 at the cell's size);
    # and bfloat16 operands move the eight up-weighted blocks little
    over_nll, over_grad = {"no-mtp": (None, 20), "no-yarn": (1, None),
                           "bf16": (20, 2)}.get(mode, (20, 20))
    if over_nll is None:
        assert numbers["nll_rms_err"] < TOL
    else:
        assert numbers["nll_rms_err"] > over_nll * TOL
    if over_grad is not None:
        assert numbers["grad_rel_err"] > over_grad * GRAD_TOL


def test_the_bias_in_the_weights_shows_in_the_bias_gradient(model, small):
    """What tells `bias-in-weight` apart whatever the rounding: the bias's
    own block of the compared gradient, zero on the program's side."""
    spec, family, cfg, params, *_ = model
    tokens, targets, (_, flat) = small
    with jax.default_matmul_precision("highest"):
        _, _, grads = ref.losses_and_grads(params, tokens, targets, spec,
                                           "bias-in-weight")
    shapes = {name: grads[name].shape for name in family.GRAD_SCALES}
    assert not _blocks(family, flat, shapes)["router_bias"].any()
    assert np.linalg.norm(np.asarray(grads["router_bias"])) > 0


def test_h_res_is_doubly_stochastic_and_the_streams_sum_is_kept(model):
    """H_res's rows and columns sum to 1, and with them
    sum_i x+[i] = sum_j x[j] + (sum_i H_post[i]) y: the mixing moves mass
    between the streams and adds the sublayer's output, nothing else."""
    _, _, cfg, params, *_ = model
    lp = jax.tree.map(lambda a: a[1], params["layers"][1][0])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 64, 128))
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 128))
    pre, post, res = tr.hc_coefficients(
        [x[:, j] for j in range(4)], lp, cfg, "hc2")
    assert pre.shape == post.shape == (4, 2, 64) and res.shape == (4, 4, 2, 64)
    # the family's draws spread the exponent by +-1.5 a token: the last
    # round's rows are exact, and the columns are where 20 rounds got to
    assert np.abs(np.asarray(res.sum(1)) - 1).max() < 1e-5   # rows
    columns = np.abs(np.asarray(res.sum(0)) - 1)
    assert np.median(columns) < 1e-5 and columns.max() < 2e-2
    # 20 rounds reach 1e-4 on both where the exponent does not lean (the
    # nearer a matrix is to a permutation, the slower Sinkhorn's rounds
    # close its columns: with the start's lean of 4 they stop at 5e-3)
    mild = {**lp, "hc2_b": 0.3 * jax.random.normal(jax.random.PRNGKey(9), (24,))}
    _, _, even = tr.hc_coefficients([x[:, j] for j in range(4)], mild, cfg,
                                    "hc2")
    assert np.abs(np.asarray(even.sum(1)) - 1).max() < 1e-4
    assert np.abs(np.asarray(even.sum(0)) - 1).max() < 1e-4
    assert float(jnp.std(even[0, 0])) > 0.01
    assert float(res.min()) > 0 and float(jnp.std(res[0, 0])) > 0.01
    assert 0 < float(pre.min()) and float(pre.max()) < 1
    assert 0 < float(post.min()) and float(post.max()) < 2
    seen = []

    def sublayer(h):
        seen.append(h)
        return y, None

    out, _ = tr._residual(x, lp, cfg, "hc2", sublayer)
    want_h = jnp.einsum("jbt,bjtd->btd", pre, x)
    assert np.abs(np.asarray(seen[0] - want_h)).max() < 1e-5
    # the streams' sum moves by H_res's column sums and the output's share
    moved = (jnp.einsum("jbt,bjtd->btd", res.sum(0), x)
             + post.sum(0)[..., None] * y)
    assert np.abs(np.asarray(out.sum(1) - moved)).max() < 2e-5
    # ... so where the columns sum to 1 it is KEPT: sum_i x+[i] =
    # sum_j x[j] + (sum_i H_post[i]) y
    out, _ = tr._residual(x, mild, cfg, "hc2", sublayer)
    _, post, _ = tr.hc_coefficients([x[:, j] for j in range(4)], mild, cfg,
                                    "hc2")
    kept = x.sum(1) + post.sum(0)[..., None] * y
    assert np.abs(np.asarray(out.sum(1) - kept)).max() < 2e-3
    assert np.abs(np.asarray(kept)).max() > 5
    one = dataclasses.replace(cfg, hc_streams=1, mtp_depth=0)
    plain, _ = tr._residual(x[:, 0], lp, one, "hc2", lambda h: (y, None))
    assert np.array_equal(np.asarray(plain), np.asarray(x[:, 0] + y))


def test_the_yarn_table_is_the_references():
    full = common.load_json("configs", CONFIG + ".json")
    for spec in (full, tiny_spec(CONFIG)):
        s = spec["rope_scaling"]
        inv, scale, grow = ref.yarn(spec)
        got = yarn_inv_freq(spec["qk_rope_head_dim"], float(spec["rope_theta"]),
                            s["factor"], s["original_max_position_embeddings"],
                            s["beta_fast"], s["beta_slow"])
        np.testing.assert_allclose(got, inv, rtol=1e-6)
        width = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
        assert scale == pytest.approx(
            width ** -0.5 * yarn_mscale(s["factor"], s["mscale_all_dim"]) ** 2)
        assert grow == 1.0
    inv = np.asarray(ref.yarn(full)[0])
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # the fast pairs keep theta's frequency, the slow ones take its 64th
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[24:], plain[24:] / 64, rtol=1e-6)
    assert (inv[11:23] < plain[11:23]).all() and (inv[11:23] > plain[11:23] / 64).all()
    assert ref.yarn(full)[1] == pytest.approx(192 ** -0.5 * 1.4159 ** 2, rel=1e-4)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 2, 8))
    yarn = (8.0, 32, 32.0, 1.0, 1.0, 1.0)
    got = stack._turn(x, None, 10000.0, yarn)
    want = ref.turn(x[0], yarn_inv_freq(8, 10000.0, *yarn[:4]))
    assert np.abs(np.asarray(got[0] - want)).max() < 1e-6
    assert np.abs(np.asarray(got - stack._turn(x, None, 10000.0))).max() > 0.1


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(model):
    """THE share test: the routed parts that the eight chips holding 1 of the
    8 experts each give, plus the shared expert counted ONCE, are the uncut
    reference's F output, and the program's part is the reference's, share by
    share, in the grouped form and in the dropless one."""
    spec, family, cfg, params, *_ = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][1][0])
    k = jax.random.split(jax.random.PRNGKey(8), 4)
    whole = {**lp, **{n: jax.random.normal(k[i], (8, *lp[n].shape[1:])) * 0.05
                      for i, n in enumerate(("w_in", "w_gate", "w_out"))}}
    h = jax.random.normal(k[3], (2, 512, 128))
    rows = h.reshape(-1, 128)
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(rows, whole, spec, first=0, held=8)
        shared = ref.gated_ffn(rows, lp["sh_in"], lp["sh_gate"], lp["sh_out"])
        parts, chosen = [], 0
        for first in range(8):
            part = {**whole, **{n: whole[n][first:first + 1]
                                for n in ("w_in", "w_gate", "w_out")}}
            share = family.model_config(
                {**spec, "n_routed_experts": 1, "held_experts_first": first},
                dtype="float32")
            assert share.counts_choices and share.experts_first == first
            routed = ref.moe(rows, part, spec, shared=False, first=first,
                             held=1)
            grouped = tr.moe_grouped(share, 2, 512, None)
            assert grouped is not None
            got, _, ids = tr._moe_ffn_grouped(h, part, share, None, *grouped)
            plain, _, _ = tr._moe_ffn_dropless_ids(h, part, share)
            assert np.abs(got.reshape(-1, 128) - routed).max() < 2e-6
            assert np.abs(plain.reshape(-1, 128) - routed).max() < 2e-6
            parts.append(np.asarray(routed))
            chosen += int(jnp.sum(ids == first))
    assert np.abs(sum(parts) + shared - uncut).max() < 5e-6
    assert np.abs(shared).max() > 1e-3 and np.abs(parts[0]).max() > 1e-4
    assert chosen == ids.size  # every choice fell on one chip's expert


def _state(cfg, params, opt):
    return {"step": jnp.zeros((), jnp.int32), "params": params,
            "opt_state": opt.init(params)}


def _batch(spec, rows=2, seed=11, T=128):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, T + 1), 3,
                              spec["vocab_size"])
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def test_a_train_step_reports_both_losses_and_moves_every_routers_bias(model):
    spec, _, cfg, params, *_ = model
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, factored=True)
    with jax.default_matmul_precision("highest"):
        state, metrics = jax.jit(make_train_step(cfg, opt))(
            _state(cfg, params, opt), _batch(spec))
        _, _, counts, _ = jax.jit(lambda p, b: forward(
            p, b["tokens"], cfg, route_counts=True,
            mtp_tokens=b["targets"]))(params, _batch(spec))
    assert counts.shape == (5, 8)  # four expert layers and the block
    assert int(counts.sum()) == 5 * 2 * 128 * 2 and int(counts[-1].min()) > 0
    step = 0.001 * np.sign(np.mean(counts, 1, keepdims=True) - counts)
    moved = state["params"]["mtp"]["layer"]["router_bias"] \
        - params["mtp"]["layer"]["router_bias"]
    np.testing.assert_allclose(moved, step[-1], atol=1e-7)
    trunk = state["params"]["layers"][1][0]["router_bias"] \
        - params["layers"][1][0]["router_bias"]
    np.testing.assert_allclose(trunk, step[:4], atol=1e-7)
    for name in ("ce_loss", "mtp_loss", "loss", "moe_choices_held",
                 "moe_rows_short"):
        assert np.isfinite(float(metrics[name]))
    assert float(metrics["moe_rows_short"]) == 0
    assert float(metrics["moe_choices_held"]) == float(counts[:, :4].sum())
    assert float(metrics["loss"]) > float(metrics["ce_loss"]) \
        + 0.09 * float(metrics["mtp_loss"])


def test_a_step_under_dp_agrees_with_one_device_and_the_axes_cover_every_leaf(
        model):
    from ray_tpu.comm.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.lm import batch_shardings

    spec, _, cfg, params, *_ = model
    axes = param_axes(cfg)
    flat, _ = jax.tree.flatten(
        axes, is_leaf=lambda a: isinstance(a, tuple) and not any(
            isinstance(e, (dict, tuple)) for e in a))
    assert [len(a) for a in flat] == [a.ndim for a in jax.tree.leaves(params)]
    layer = axes["layers"][1][0]
    assert layer["wq_b"] == (None, None, "heads", None)
    assert layer["wkv_a"] == (None, "embed", None)
    assert layer["hc1_phi"] == (None,) * 4
    assert axes["mtp"]["layer"]["wo"] == ("heads", None, "embed")
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, factored=True)
    batch = _batch(spec)
    with jax.default_matmul_precision("highest"):
        want, want_metrics = jax.jit(make_train_step(cfg, opt))(
            _state(cfg, params, opt), batch)
        mesh = build_mesh(MeshSpec.create(dp=2), devices=jax.devices()[:2])
        shardings = tree_shardings(axes, mesh)
        with mesh:
            placed = jax.device_put(params, shardings)
            got, got_metrics = jax.jit(make_train_step(cfg, opt))(
                _state(cfg, placed, opt),
                jax.device_put(batch, batch_shardings(mesh)))
    for name in ("loss", "ce_loss", "mtp_loss"):
        assert abs(float(got_metrics[name]) - float(want_metrics[name])) < 1e-5
    for a, b in zip(jax.tree.leaves(got["params"]),
                    jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_a_latent_stack_trains_and_the_double_block_is_still_refused():
    for name in ("tiny-kanana", "kanana-2-30b-a3b", "tiny-xing4",
                 "xing4.0-29b-a4b", "tiny-trinity"):
        assert get_config(name).untrainable == ""
    said = get_config("tiny-longcat-flash").untrainable
    assert "'mla2'" in said and "ops/mla_attention.py" in said
    assert "'attn', 'swa', 'mla'" in said
    with pytest.raises(NotImplementedError):
        param_axes(get_config("tiny-longcat-flash"))
    cfg = get_config("tiny-kanana")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 512)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, factored=True)
    _, metrics = jax.jit(make_train_step(cfg, opt))(
        _state(cfg, params, opt),
        {"tokens": toks[:, :-1], "targets": toks[:, 1:]})
    assert np.isfinite(float(metrics["loss"])) and float(metrics["grad_norm"]) > 0
    assert "mtp_loss" not in metrics


@pytest.mark.parametrize("fields, says", [
    (dict(hc_streams=4, layer_kinds=("mla2",) * 2), "hc_streams"),
    (dict(mtp_depth=2), "mtp_depth"),
    (dict(hc_streams=0), "hc_streams"),
    (dict(rope_yarn=(8.0, 32, 32.0, 1.0, 1.0)), "rope_yarn"),
])
def test_what_a_mode_cannot_honour_is_refused_by_name(fields, says):
    base = "tiny-longcat-flash" if "layer_kinds" in fields else "tiny-xing4"
    with pytest.raises(ValueError) as e:
        get_config(base, **fields)
    assert says in str(e.value)
    with pytest.raises(ValueError) as e:
        get_config("tiny-trinity", rope_yarn=(8.0, 32, 32.0, 1.0, 1.0, 1.0))
    assert "rope_yarn" in str(e.value)


def test_the_serve_modes_of_a_stack_with_streams_are_refused_by_name():
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = get_config("tiny-xing4")
    with pytest.raises(ValueError) as e:
        InferenceEngine.abstract(cfg, EngineConfig(
            max_batch_size=2, page_size=4, max_pages=16, max_seq_len=32,
            prefill_chunk=16, cache_dtype="float32"))
    assert "hc_streams" in str(e.value) and "trained, not served" in str(e.value)


# sha256 of the StableHLO text of three ONE-stream train steps (2 x 64
# tokens, the factored optimizer, `highest`), taken on the parent of PR 58
# (b350d82) with jax as engine_programs pins it: the residual path enters
# the function every program runs, and with one stream it emits what stood
# there, op for op. The serve programs' texts are engine_programs.PINNED's
# (tests/test_smallthinker_model.py holds every one to its digest); the
# latent kind's own serve programs, which that table lacks, are pinned here
TRAIN_PINNED = {
    "tiny-trinity":
        "233f0a76b0a23400ffa5fb60d00cc01800f5c9edc3fbb93a9f5452a10b80b08f",
    "tiny-llama":
        "6f92d40689ef4b414856eabce663f49c16d5e9d87372fa934b35252c120380cf",
    "tiny-moe":
        "f1f72feae188da7263801a79cfc8ce20ad087ce697808bae8920f1fa9fb7da3c",
}
KANANA_PINNED = {
    "decode": "7adfae745fb672d8fd046a5eba6e9b1b1e08a94f6884729a7c889f516bdd1bec",
    "chunk": "96b90c09a20bb1a38848101bb339a3be621925376b61dfc98bd71e72d090ee10",
    "bucket": "9f66b2e3fb164184a5790a4a102e3f9861b5da7cc25fc5020a26f677b5f0d3d6",
}


@pytest.mark.parametrize("name", sorted(TRAIN_PINNED))
def test_a_one_stream_train_step_lowers_to_the_parents_text(name):
    if jax.__version__ != LOWERED_WITH_JAX:
        pytest.skip(f"digests were taken with jax {LOWERED_WITH_JAX}")
    cfg = get_config(name)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=10,
                         factored=True)
    state = jax.eval_shape(
        lambda k: _state(cfg, init_params(cfg, k), opt), jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
             for k in ("tokens", "targets")}
    with jax.default_matmul_precision("highest"):
        text = jax.jit(make_train_step(cfg, opt)).lower(state, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == TRAIN_PINNED[name]


@pytest.mark.parametrize("program", sorted(KANANA_PINNED))
def test_the_latent_kinds_serve_programs_lower_to_the_parents_text(program):
    assert digest("tiny-kanana", program) == KANANA_PINNED[program]


def test_the_layer_loop_keeps_the_narrow_values_under_streams(model):
    """What a layer keeps under `remat` with four streams: the three latents,
    the flash output at the kernel's width, the attention sublayer's output
    and both sublayers' raw coefficients; never the 4-wide stream."""
    from ray_tpu.util import profiler

    spec, _, cfg, params, *_ = model
    cfg = dataclasses.replace(cfg, remat=True)
    batch = _batch(spec, T=T)
    jax.jit(jax.grad(lambda p: tr.loss_fn(p, batch, cfg)[0])).lower(params)
    _, bound = tr.moe_grouped(cfg, 2, T, None)
    kept = {name: profiler._g_remat_kept.get({"name": name})
            for name in stack.KEPT_UNDER_REMAT}
    blocks, act = 6, 4
    assert kept["attn_half"] == kept["attn_q"] == kept["attn_k"] == 0
    assert kept["attn_out"] == blocks * 2 * T * 128 * act
    assert kept["mhc_coef"] == blocks * 2 * T * 2 * 24 * 4
    assert kept["mla_cq"] == blocks * 2 * T * 32 * act
    assert kept["mla_c"] == blocks * 2 * T * 16 * act
    assert kept["mla_kr"] == blocks * 2 * T * 8 * act
    assert kept["flash_out"] == blocks * 2 * T * 4 * 128 * act
    assert kept["moe_up"] == kept["moe_gate"] == 5 * bound * 128 * act
    # the tokens' choices, so that the backward's sort is the forward's
    assert kept["moe_weights"] == kept["moe_choice"] == 5 * 2 * T * 2 * 4
    batch = _batch(spec)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: tr.loss_fn(
            p, batch, dataclasses.replace(cfg, remat=False))[0]))(params)
        got = jax.jit(jax.grad(lambda p: tr.loss_fn(p, batch, cfg)[0]))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_configuration_is_the_catalogs_row_but_for_its_four_cuts():
    import json

    spec = common.load_json("configs", CONFIG + ".json")
    manifest = common.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cuts = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
            "vocab_size"]
    assert entry["reduced"] == cuts == list(spec["reduced"])
    assert entry["source"] == spec["source"]
    assert 1 <= len(entry["why"]) <= 200
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == spec["source"])
    except OSError:
        pytest.skip("no catalog beside the guides here")
    differs = {k for k, v in row["config"].items() if spec.get(k, "-") != v}
    assert differs == set(cuts)
    assert spec["n_routed_experts_total"] == row["config"]["n_routed_experts"]
    assert spec["rope_scaling"] == row["config"]["rope_scaling"]
    for key in cuts:
        assert spec["published"][key] == row["config"][key]
    assert spec["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert spec["n_routed_experts"] * 8 == row["config"]["n_routed_experts"]
    for item in ("streams", "mhc_norm", "mhc_maps", "hc_eps_places",
                 "sinkhorn_order", "rope_interleave", "yarn", "router_bias",
                 "router_bias_update_rate", "mtp_meeting", "mtp_loss_weight",
                 "weights", "torch_dtype", "train_recipe"):
        assert item in spec["assumed"]


def test_the_cell_is_an_entry_and_its_readers_list_it():
    """Entries are found by name: a later PR appends behind them."""
    manifest = common.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "train-packed-x2", "chips": 1,
                     "why": entry["why"]}
    assert 1 <= len(entry["why"]) <= 200
    cell = common.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    own = ["mhc_device_share.train", "mhc_stream_roofline.train"]
    names = [m["name"] for m in cell["per_layer"]]
    assert names[-2:] == own
    trinity = [m["name"] for m in common.load_cell(
        "trinity-mini.train-packed-x4")["per_layer"]]
    assert names[:-2] == [n for n in trinity if "flash_window" not in n]
    for m in manifest["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s"
            assert callable(common.load_reader(m["name"]))
    assert cell["traffic"]["rows_per_step"] == cell["check"]["rows"] == 2
    packed = common.load_json("traffic", "train-packed-x4.json")
    assert {k: v for k, v in cell["traffic"].items()
            if k not in ("rows_per_step", "why")} == {
        k: v for k, v in packed.items() if k not in ("rows_per_step", "why")}
    assert cell["recipe"] == common.load_cell("mistral-7b.train-packed")["recipe"]
    assert cell["corpus_rows"] == 384


def test_the_work_is_a_hand_count_at_the_cells_shapes():
    spec = common.load_json("configs", CONFIG + ".json")
    family = common.family(spec)
    pairs = 8192 * 8193 / 2
    fwd = family.work["flash_fwd"](spec, 2, 8192)
    assert fwd["flops"] == 2 * 2 * 32 * pairs * (192 + 128)
    assert fwd["bytes"] == 2 * 8192 * 32 * (2 * 192 + 2 * 128) * 2
    bwd = family.work["flash_bwd"](spec, 2, 8192)
    assert bwd["flops"] == 2 * 2 * 32 * pairs * (3 * 192 + 2 * 128)
    moe = family.work["moe_grouped"](spec, 8192)
    assert moe["flops"] == 9 * 2 * 8192 * 3584 * 1024
    mhc = family.work["mhc"](spec, 2, 8192)
    # 14 rows of 3584 a token and sublayer forward (100 kB), as much back
    assert mhc["bytes"] == 16384 * 12 * 2 * 14 * 3584 * 2
    per_token = family.train_flops_per_token(spec, 8192)
    assert 4.3e9 < per_token < 4.8e9  # the issue's 4.5 GFLOP a token
    assert family.calls_per_pass(spec, "flash_fwd") == 6
    assert family.calls_per_pass(spec, "moe_grouped") == 5
    assert family.held_experts(spec) == 8
    cfg = family.model_config(spec)
    assert cfg.param_count() == pytest.approx(913.6e6, rel=2e-3)
    assert tr.moe_grouped(cfg, 2, 8192, None) is not None


def test_the_cpu_rehearsal_runs_the_new_cell(monkeypatch, capsys):
    """`xing4.0-29b-a4b.train-packed-x2` end to end at the family's tiny cut:
    the benchmark's own train driver through `JaxTrainer.fit()`, a Dataset
    feeding it, the window, and `checks.train` against the plain reference
    under the cell's OWN limits, the timed step's loss among what is
    compared; the joined readers read the first step's metrics."""
    import argparse

    import ray_tpu
    from benchmark import drive, train_driver
    from benchmark.tests.tiny import tiny_cell

    cell = tiny_cell(CELL)
    assert cell["check"]["rows"] == cell["traffic"]["rows_per_step"] == 2
    cell["traffic"].update(row_tokens=512)
    cell["traffic"]["doc_len"].update(median=60, max=512)
    monkeypatch.setattr(common, "require_tpu", lambda chips: None)
    monkeypatch.setattr(common, "memory_peak_bytes", lambda chips: 0)
    monkeypatch.setattr(common, "wait_for_free_memory", lambda chips: 0)
    args = argparse.Namespace(seed=2 ** 31 + 58, seconds=1.0, trace=0)
    registry.fresh()
    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    out = train_driver.run(cell, args, {"kind": "cpu"}, common.CompileWatch(),
                           0.0, None)  # shuts the runtime down itself
    assert out["correct"], out
    said = capsys.readouterr().out
    assert all(f'"check": "{name}"' in said for name in cell["check"]["limits"])
    assert '"check": "step_loss_err"' in said
    assert out["attempted"] >= 1 and out["run"]["compiles_in_window"] == 0
    assert out["run"]["first_metrics"]["mtp_loss"] > 0
    ctx = {"cell": cell, "spec": cell["config"], "chips": 1,
           "family": common.family(cell["config"]), "run": out["run"],
           "trace": {"ops": {}, "modules": {}, "busy_s": 0.0}}
    assert 1.0 <= common.load_reader("moe_rows_max_over_mean.train")(ctx) < 3.0
    assert 5 < common.load_reader("moe_row_buffer_fill_share.train")(ctx) <= 100
    for name in ("mhc_device_share.train", "mhc_stream_roofline.train",
                 "moe_grouped_roofline.train", "moe_ffn_device_share.train",
                 "flash_attn_device_share.train"):
        assert common.load_reader(name)(ctx) is None  # no such op in the trace
    del drive
