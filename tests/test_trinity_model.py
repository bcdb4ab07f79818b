"""The Trinity-Mini stack TRAINED (the `afmoe` family's tiny cut: gated GQA
with normalised queries and keys, rotary over a window in three layers of
four and no positions in the fourth, a norm on both sides of every sublayer,
a leading dense layer, then a share of sigmoid-routed experts beside a shared
one) against the plain reference of its family (benchmark/reference/
trinity.py: float32, `highest`, no kernel, no sorting, nothing imported from
the program), on seeded weights: the loss and every compared gradient leaf,
per control mode; the share test; the router's bias under two train steps;
`make_train_step` under `dp` and `fsdp` against one device; and the ENGINE's
prefill then decode against the reference's full forward.

Tolerances. Weights are the family's draws cast to float32 and the tiny
model runs in float32, so program and reference differ only in the order of
float32 sums: 2e-6 on a row's loss and 5e-6 on a gradient block were the
largest seen; the limits are TOL. Every control mode lands far outside."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, common
from benchmark.reference import trinity as ref
from benchmark.tests.tiny import tiny_spec
from ray_tpu.core.metrics import registry
from ray_tpu.models import forward, get_config, init_params, param_axes, stack
from ray_tpu.models import transformer as tr
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from ray_tpu.train.lm import make_optimizer, make_train_step

TOL = 5e-5
CONFIG = "trinity-mini"
CELL = CONFIG + ".train-packed-x4"
T = 512  # rows enough an expert for the grouped form (`moe_grouped`)


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec(CONFIG)
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(54)))
    cfg = family.model_config(spec, dtype="float32")
    toks = jax.random.randint(jax.random.PRNGKey(5), (T + 1,), 3,
                              spec["vocab_size"])
    return spec, family, cfg, params, toks[:-1], toks[1:]


@pytest.fixture(scope="module")
def compared(model):
    spec, family, cfg, params, tokens, targets = model
    with jax.default_matmul_precision("highest"):
        return (family.program_probe(cfg, params, tokens, targets),
                family.nll_and_norm_grads(params, tokens, targets, spec))


def test_the_cut_is_a_dense_window_layer_and_one_period(model):
    spec, _, cfg, params, *_ = model
    assert cfg.layer_kinds == ("swa", "swa", "swa", "swa", "attn")
    assert cfg.second_halves == ("ffn", "moe", "moe", "moe", "moe")
    assert cfg.segments() == ((0, ("swa",), 1), (1, ("swa",), 3),
                              (4, ("attn",), 1))
    assert cfg.norm_place == "both" and not cfg.post_norm
    assert (cfg.num_experts, cfg.router_width, cfg.experts_first) == (4, 8, 0)
    lp = params["layers"][1][0]
    assert {"ln1", "ln1_post", "ln2", "ln2_post", "wg", "q_norm", "k_norm",
            "router", "router_bias", "sh_in"} <= set(lp)
    assert lp["router"].shape == (3, 128, 8) and lp["w_in"].shape[:2] == (3, 4)
    assert tr.moe_grouped(cfg, 1, T, None) == (128, 1536)


def test_the_sandwich_is_one_field_that_post_norm_cannot_contradict():
    cfg = get_config("tiny-trinity")
    assert cfg.norm_place == "both" and cfg.post_norm is False
    olmo = get_config("tiny-olmo-hybrid")
    assert olmo.norm_place == "post" and olmo.post_norm is True
    assert dataclasses.replace(olmo, n_layers=8).norm_place == "post"
    with pytest.raises(ValueError, match="post_norm"):
        dataclasses.replace(cfg, post_norm=True)
    with pytest.raises(ValueError, match="norm_place"):
        dataclasses.replace(cfg, norm_place="sandwich")
    assert get_config("tiny-llama").norm_place == "pre"


def test_the_loss_agrees_with_the_plain_reference(compared):
    (nll, _), (ref_nll, _) = compared
    assert float(jnp.max(jnp.abs(nll - ref_nll))) < TOL
    assert 5.5 < float(jnp.mean(ref_nll)) < 7.5  # about ln(512)


BLOCKS = {"ln1": 5 * 128, "w_in": 4 * 128 * 128, "w_gate": 4 * 128 * 128,
          "w_out": 4 * 128 * 128, "router": 128 * 8}


def _blocks(flat):
    flat, out, at = np.asarray(flat, np.float64), {}, 0
    for name, size in BLOCKS.items():
        out[name] = flat[at:at + size]
        at += size
    assert at == flat.size
    return out


@pytest.mark.parametrize("leaf", list(BLOCKS))
def test_every_gradient_leaf_agrees_with_the_plain_reference(compared, leaf):
    """The first norms' weights (every layer's backward) and the leaves only
    the grouped product's backward reaches: the held experts' three
    matrices (the weights' gradient) and the router's matrix (through the
    float32 combine)."""
    (_, g), (_, ref_g) = compared
    got, want = _blocks(g)[leaf], _blocks(ref_g)[leaf]
    assert np.linalg.norm(want) > 1e-4
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < TOL


@pytest.mark.parametrize("mode", ref.EQUATION_MODES + ref.PRECISION_MODES)
def test_the_program_is_held_apart_from_each_control_mode(model, compared, mode):
    spec, family, _, params, tokens, targets = model
    (nll, g), _ = compared
    with jax.default_matmul_precision("highest"):
        low_nll, low_g = family.nll_and_norm_grads(params, tokens, targets,
                                                   spec, mode)
    numbers = checks.train_numbers(nll, g, low_nll, low_g)
    assert numbers["nll_rms_err"] > 50 * TOL
    assert numbers["grad_rel_err"] > 50 * TOL


def test_a_zero_weights_gradient_and_a_window_off_by_one_fail_the_limit(
        model, compared):
    """What `grad_rel_err` must catch of the new backward: the weights'
    gradient of the grouped product left at zero, and a window one key off."""
    spec, family, _, params, tokens, targets = model
    (_, g), (ref_nll, ref_g) = compared
    limit = common.load_cell(CELL)["check"]["limits"]["grad_rel_err"]
    blocks = _blocks(g)
    zeroed = np.concatenate([np.zeros_like(v) if n in ("w_in", "w_gate", "w_out")
                             else v for n, v in blocks.items()])
    assert checks.train_numbers(ref_nll, zeroed, ref_nll, ref_g)[
        "grad_rel_err"] > limit
    off = {**spec, "sliding_window": spec["sliding_window"] + 1}
    with jax.default_matmul_precision("highest"):
        _, off_g = family.nll_and_norm_grads(params, tokens, targets, off)
    assert checks.train_numbers(ref_nll, g, ref_nll, off_g)[
        "grad_rel_err"] > limit


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(model):
    """THE share test: the routed parts that the chips holding 2 of the 8
    experts each give, plus the shared expert counted ONCE, are the uncut
    reference's layer, and the program's part is the reference's, share by
    share, in the grouped form and in the dropless one."""
    spec, family, cfg, params, *_ = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][1][0])
    k = jax.random.split(jax.random.PRNGKey(8), 4)
    whole = {**lp, **{n: jax.random.normal(k[i], (8, *lp[n].shape[1:])) * 0.05
                      for i, n in enumerate(("w_in", "w_gate", "w_out"))}}
    h = jax.random.normal(k[3], (2, 256, 128))
    rows = h.reshape(-1, 128)
    uncut_spec = {**spec, "num_experts": 8, "experts_first": 0}
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe(rows, whole, uncut_spec)
        shared = ref.gated_ffn(rows, lp["sh_in"], lp["sh_gate"], lp["sh_out"])
        parts, chosen = [], 0
        for first in (0, 2, 4, 6):
            part = {**whole, **{n: whole[n][first:first + 2]
                                for n in ("w_in", "w_gate", "w_out")}}
            share = family.model_config(
                {**spec, "num_experts": 2, "experts_first": first},
                dtype="float32")
            assert share.counts_choices and share.experts_first == first
            routed = ref.moe(rows, part, uncut_spec, shared=False,
                             first=first, held=2)
            grouped = tr.moe_grouped(share, 2, 256, None)
            assert grouped is not None
            got, _, ids = tr._moe_ffn_grouped(h, part, share, None, *grouped)
            plain, _, _ = tr._moe_ffn_dropless_ids(h, part, share)
            assert np.abs(got.reshape(-1, 128) - routed).max() < 2e-6
            assert np.abs(plain.reshape(-1, 128) - routed).max() < 2e-6
            parts.append(np.asarray(routed))
            chosen += int(jnp.sum((ids >= first) & (ids < first + 2)))
    assert np.abs(sum(parts) + shared - uncut).max() < 5e-6
    assert np.abs(shared).max() > 1e-3 and np.abs(parts[0]).max() > 1e-4
    assert chosen == ids.size  # every choice fell on one chip's experts


def _state(cfg, params, opt):
    return {"step": jnp.zeros((), jnp.int32), "params": params,
            "opt_state": opt.init(params)}


def _batch(spec, rows=2, seed=11):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (rows, T + 1), 3,
                              spec["vocab_size"])
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def test_two_train_steps_move_the_bias_by_the_rule(model):
    """b_e += rate * sign(mean(n) - n_e) over ALL the router's outputs,
    after the optimizer's update; no gradient, no optimizer update; and the
    step's numbers: the choices that fell on held experts are the rows the
    grouped products ran over. They leave the step in its metrics alone (no
    host callback in the program), and reach the counters where a loop
    reports them."""
    spec, _, cfg, params, *_ = model
    from ray_tpu.train.session import TrainContext, _TrainSession

    cfg = dataclasses.replace(cfg, remat=True)
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, factored=True)
    step = jax.jit(make_train_step(cfg, opt))
    session = _TrainSession(TrainContext())
    state, batch = _state(cfg, params, opt), _batch(spec)
    registry.fresh()
    for _ in range(2):
        before = state["params"]
        _, _, counts = jax.jit(lambda p, t: forward(
            p, t, cfg, route_counts=True))(before, batch["tokens"])
        state, metrics = step(state, batch)
        assert all(np.ndim(v) == 0 for v in metrics.values())
        n = np.asarray(counts, np.float64)
        assert float(metrics["moe_choices_held"]) == n[:, :4].sum()
        assert float(metrics["moe_rows_max"]) == n[:, :4].max(1).sum()
        assert n.shape == (4, 8) and (n.sum(1) == 2 * 2 * T).all()
        want = cfg.router_bias_rate * np.sign(n.mean(1, keepdims=True) - n)
        for s, i, rows in stack.expert_layers(cfg):
            moved = (state["params"]["layers"][s][i]["router_bias"]
                     - before["layers"][s][i]["router_bias"])
            np.testing.assert_allclose(moved, want[np.asarray(rows)], atol=1e-7)
        session.report({k: float(v) for k, v in metrics.items()})
    assert "callback" not in step.lower(state, batch).as_text()
    read = {name: value for metric in registry._metrics.values()
            for name, _, value in metric.samples()
            if name.startswith("train_moe")}
    assert read["train_moe_steps"] == 2
    tile, bound = tr.moe_grouped(cfg, 2, T, None)
    assert read["train_moe_rows_bound"] == 2 * 4 * bound
    held = n[:, :4]
    assert read["train_moe_choices_held"] > 0.3 * 2 * 4 * 2 * 2 * T
    assert read["train_moe_rows_max"] >= read["train_moe_choices_held"] / 4
    assert 0 < read["train_moe_bias_moved"] < 2 * 4 * 8
    assert "train_moe_rows_overflow" not in read
    del held, tile


@pytest.mark.parametrize("axes", [{"dp": 2}, {"fsdp": 2}])
def test_a_sharded_train_step_agrees_with_one_device(model, axes):
    from ray_tpu.comm.mesh import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import tree_shardings
    from ray_tpu.train.lm import batch_shardings

    spec, _, cfg, params, *_ = model
    opt = make_optimizer(learning_rate=1e-3, warmup_steps=1, factored=True)
    batch = _batch(spec)
    with jax.default_matmul_precision("highest"):
        want, want_metrics = jax.jit(make_train_step(cfg, opt))(
            _state(cfg, params, opt), batch)
        mesh = build_mesh(MeshSpec.create(**axes), devices=jax.devices()[:2])
        shardings = tree_shardings(param_axes(cfg), mesh)
        with mesh:
            placed = jax.device_put(params, shardings)
            got, got_metrics = jax.jit(make_train_step(cfg, opt))(
                _state(cfg, placed, opt),
                jax.device_put(batch, batch_shardings(mesh)))
    assert abs(float(got_metrics["loss"]) - float(want_metrics["loss"])) < 1e-5
    for a, b in zip(jax.tree.leaves(got["params"]),
                    jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_what_cannot_be_trained_yet_is_refused_by_kind():
    olmo = get_config("tiny-olmo-hybrid")
    opt = make_optimizer()
    for refused in (lambda: param_axes(olmo),
                    lambda: make_train_step(olmo, opt)):
        with pytest.raises(NotImplementedError) as e:
            refused()
        assert "'attn', 'swa'" in str(e.value) and "ops/gdn.py" in str(e.value)
    said = get_config("tiny-longcat-flash").untrainable
    assert "ops/mla_attention.py" in said
    assert "ops/ssm.py" in get_config("tiny-sambay").untrainable
    assert "ops/ssd.py" in get_config("tiny-granite-hybrid").untrainable
    for name in ("tiny-trinity", "tiny-smallthinker", "trinity-mini"):
        assert get_config(name).untrainable == ""
    axes = param_axes(get_config("tiny-trinity"))
    shapes = jax.eval_shape(
        lambda k: init_params(get_config("tiny-trinity"), k),
        jax.random.PRNGKey(0))
    flat, _ = jax.tree.flatten(
        axes, is_leaf=lambda a: isinstance(a, tuple) and not any(
            isinstance(e, (dict, tuple)) for e in a))
    assert [len(a) for a in flat] == [len(s.shape) for s in jax.tree.leaves(shapes)]


def test_the_layer_loop_keeps_its_names_under_remat(model):
    from ray_tpu.util import profiler

    spec, _, cfg, params, *_ = model
    cfg = dataclasses.replace(cfg, remat=True)
    batch = _batch(spec)
    jax.jit(jax.grad(lambda p: tr.loss_fn(p, batch, cfg)[0])).lower(params)
    tile, bound = tr.moe_grouped(cfg, 2, T, None)
    kept = {name: profiler._g_remat_kept.get({"name": name})
            for name in stack.KEPT_UNDER_REMAT}
    assert kept["attn_half"] == 5 * 2 * T * 128 * 4
    assert kept["flash_out"] == 5 * 2 * T * 4 * 32 * 4
    assert kept["moe_up"] == kept["moe_gate"] == 4 * bound * 128 * 4
    del tile


def engine_for(cfg, params, **kw):
    ecfg = dict(max_batch_size=2, page_size=4, max_pages=129,
                max_window_pages=40, max_seq_len=96, prefill_buckets=(8, 16),
                prefill_chunk=16, decode_span=4, busy_span=2,
                cache_dtype="float32")
    ecfg.update(kw)
    return InferenceEngine(params, cfg, EngineConfig(**ecfg))


def _logprobs(logits):
    logits = np.asarray(logits, np.float64)
    top = logits.max(-1, keepdims=True)
    return logits - top - np.log(np.exp(logits - top).sum(-1, keepdims=True))


@pytest.mark.parametrize("length", [5, 40], ids=["bucket", "chunked"])
def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(
        model, length):
    """The stack's serve modes get the sandwich norms for nothing: the
    registered `tiny-trinity` (two periods, every expert held) on both
    prefill paths, then 30 decoded tokens through both page spaces (past
    the window of 16), against the reference's one cache-less pass; the
    reference without the norms AFTER the sublayers is far off."""
    spec, family, *_ = model
    cfg = get_config("tiny-trinity")
    spec = {**spec, "num_hidden_layers": 8, "num_dense_layers": 2,
            "num_experts": 8, "layer_types": [
                "full_attention" if k == "attn" else "sliding_attention"
                for k in cfg.layer_kinds]}
    assert dataclasses.replace(
        family.model_config(spec, dtype="float32", remat=False),
        name=cfg.name, n_routed_experts=0) == cfg
    params = stack.init_params(cfg, jax.random.PRNGKey(7))
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(length), (length,), 3, spec["vocab_size"])]
    eng = engine_for(cfg, params)
    try:
        got = eng.generate(prompt, max_tokens=30)
    finally:
        eng.stop()
    seq = prompt + list(got["token_ids"])
    padded = np.zeros((128,), np.int32)
    padded[:len(seq)] = seq
    at = len(prompt) - 1 + np.arange(30)
    picked = (np.arange(30), got["token_ids"])
    served = np.asarray(got["logprobs"])
    with jax.default_matmul_precision("highest"):
        want, low = (_logprobs(family.logits_at(
            params, jnp.asarray(padded), jnp.asarray(at), spec, mode))
            for mode in (None, "pre-norm-only"))
    assert np.abs(served - want[picked]).max() < TOL
    assert np.abs(served - low[picked]).max() > 100 * TOL


def test_the_configuration_is_the_catalogs_row_but_for_its_five_cuts():
    import json

    spec = common.load_json("configs", CONFIG + ".json")
    manifest = common.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cuts = ["num_hidden_layers", "num_dense_layers", "layer_types",
            "num_experts", "vocab_size"]
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
    assert spec["num_experts_routed"] == row["config"]["num_experts"]
    assert spec["layer_types"] == (
        row["config"]["layer_types"][:1] + row["config"]["layer_types"][4:8])
    for key in ("num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size"):
        assert spec["published"][key] == row["config"][key]
    assert spec["vocab_size"] * 8 == row["config"]["vocab_size"]
    for item in ("head_gate", "qk_norm", "positions", "norms", "router_bias",
                 "embedding", "bias_update", "weights", "torch_dtype"):
        assert item in spec["assumed"]


def test_the_cell_is_an_entry_and_its_readers_list_it():
    """Entries are found by name: a later PR appends behind them."""
    manifest = common.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG,
                     "traffic": "train-packed-x4", "chips": 1,
                     "why": entry["why"]}
    assert 1 <= len(entry["why"]) <= 200
    cell = common.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    own = ["flash_window_fwd_roofline.train", "flash_window_bwd_roofline.train",
           "moe_grouped_roofline.train", "moe_ffn_device_share.train",
           "flash_attn_device_share.train", "moe_rows_max_over_mean.train",
           "moe_row_buffer_fill_share.train"]
    names = [m["name"] for m in cell["per_layer"]]
    assert names[-7:] == own
    assert set(names[:-7]) == {m["name"] for m in
                               common.load_cell("mistral-7b.train-packed")["per_layer"]}
    later = "xing4.0-29b-a4b.train-packed-x2"  # PR 58 joined all but the window's
    for m in manifest["per_layer"]:
        if m["name"] in own:
            assert m["workloads"] == [CELL] + [later] * (
                "flash_window" not in m["name"])
            assert m["moves"] == "train_tokens_per_s"
            assert callable(common.load_reader(m["name"]))
    assert cell["traffic"]["rows_per_step"] == 4
    assert cell["recipe"] == common.load_cell("mistral-7b.train-packed")["recipe"]


def test_the_work_is_a_hand_count_at_the_cells_shapes():
    spec = common.load_json("configs", CONFIG + ".json")
    family = common.family(spec)
    assert family.window_pairs(8192, 2048) == 2048 * 2049 / 2 + 6144 * 2048
    fwd = family.work["flash_window_fwd"](spec, 4, 8192)
    assert fwd["flops"] == 4 * 2 * 2 * 32 * 128 * family.window_pairs(8192, 2048)
    full = family.work["flash_fwd"](spec, 4, 8192)
    assert full["flops"] == 4 * 2 * 2 * 32 * 128 * 8192 * 8193 / 2
    assert full["bytes"] == 4 * 8192 * 128 * 2 * 36 * 2 < fwd["bytes"]
    moe = family.work["moe_grouped"](spec, 32768)
    assert moe["flops"] == 9 * 2 * 32768 * 2048 * 1024
    per_token = family.train_flops_per_token(spec, 8192)
    assert 2.1e9 < per_token < 2.4e9  # the issue's 2.2 GFLOP a token
    assert family.calls_per_pass(spec, "flash_window_fwd") == 4
    assert family.calls_per_pass(spec, "flash_fwd") == 1
    assert family.calls_per_pass(spec, "moe_grouped") == 4
    assert family.held_experts(spec) == 16
    cfg = family.model_config(spec)
    assert cfg.param_count() == pytest.approx(705.5e6, rel=2e-3)
    assert tr.moe_grouped(cfg, 4, 8192, None) == (512, 73728)


def test_the_cpu_rehearsal_runs_the_new_cell(monkeypatch, capsys):
    """`trinity-mini.train-packed-x4` end to end at the family's tiny cut:
    the benchmark's own train driver through `JaxTrainer.fit()`, a Dataset
    feeding it, the window, and `checks.train` against the plain reference
    under the cell's OWN limits, the timed step's loss among what is
    compared; the joined readers read the first step's metrics."""
    import argparse

    import ray_tpu
    from benchmark import drive, train_driver
    from benchmark.tests.tiny import tiny_cell

    cell = tiny_cell(CELL)
    # every row of the step is checked, so the TIMED step's loss is compared
    assert cell["check"]["rows"] == cell["traffic"]["rows_per_step"] == 4
    cell["traffic"].update(row_tokens=256, rows_per_step=2)
    cell["traffic"]["doc_len"].update(median=60, max=256)
    monkeypatch.setattr(common, "require_tpu", lambda chips: None)
    monkeypatch.setattr(common, "memory_peak_bytes", lambda chips: 0)
    monkeypatch.setattr(common, "wait_for_free_memory", lambda chips: 0)
    args = argparse.Namespace(seed=2 ** 31 + 7, seconds=1.0, trace=0)
    registry.fresh()
    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    out = train_driver.run(cell, args, {"kind": "cpu"}, common.CompileWatch(),
                           0.0, None)  # shuts the runtime down itself
    assert out["correct"], out
    said = capsys.readouterr().out
    assert all(f'"check": "{name}"' in said for name in cell["check"]["limits"])
    assert '"check": "step_loss_err"' in said
    assert out["attempted"] >= 1 and out["run"]["compiles_in_window"] == 0
    ctx = {"cell": cell, "spec": cell["config"], "chips": 1,
           "family": common.family(cell["config"]), "run": out["run"],
           "trace": {"ops": {}, "modules": {}, "busy_s": 0.0}}
    assert 1.0 <= common.load_reader("moe_rows_max_over_mean.train")(ctx) < 3.0
    assert 5 < common.load_reader("moe_row_buffer_fill_share.train")(ctx) <= 100
    for name in ("flash_window_fwd_roofline.train", "moe_grouped_roofline.train",
                 "moe_ffn_device_share.train", "flash_attn_device_share.train"):
        assert common.load_reader(name)(ctx) is None  # no such op in the trace
    del drive
