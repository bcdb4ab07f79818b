"""What `cfg.remat` keeps (models/transformer.py `_remat`): the attention
half of a layer by name, so the backward runs no attention kernel or
projection twice, and of a dense gated FFN's `gate` and `up` products as
many as `kept_under_remat` finds room for on the device (none where the
backend reports no memory, as here). CPU, float32 tiny presets: sizes and
jaxprs, never times."""

import contextlib
import dataclasses
import functools
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax._src.core import jaxprs_in_params

from ray_tpu.comm.mesh import MeshSpec, build_mesh
from ray_tpu.core.logging import get_logger
from ray_tpu.models import get_config, init_params, loss_fn
from ray_tpu.models import transformer
from ray_tpu.models.transformer import forward_pp
from ray_tpu.ops import attention, flash_attention
from ray_tpu.parallel.sharding import no_constrain, split_ways
from ray_tpu.util import profiler

B, T = 2, 32


def _batch(cfg, rows=B):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, T), 0,
                                cfg.vocab_size)
    return {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}


# case -> (preset, the forward that holds the layer loop)
CASES = {
    "dense": ("tiny-llama", None),
    "moe": ("tiny-moe", None),
    "forward_pp_body": ("tiny-llama", "pp"),
    "forward_pp_body_moe": ("tiny-moe", "pp"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_gradients_under_remat_are_those_without(case, cpu_mesh_devices):
    preset, loop = CASES[case]
    cfg = dataclasses.replace(get_config(preset), n_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, rows=4)
    forward_fn, mesh = None, None
    if loop == "pp":
        mesh = build_mesh(MeshSpec.create(dp=2, pp=2),
                          devices=cpu_mesh_devices[:4])
        forward_fn = functools.partial(forward_pp, mesh=mesh,
                                       num_microbatches=2)
    got = {}
    for remat in (True, False):
        c = dataclasses.replace(cfg, remat=remat)
        with mesh if mesh is not None else contextlib.nullcontext():
            got[remat] = jax.jit(jax.grad(lambda p: loss_fn(
                p, batch, c, forward_fn=forward_fn)[0]))(params)
    flat = jax.tree.leaves(got[False])
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in flat)
    for kept, plain in zip(jax.tree.leaves(got[True]), flat):
        np.testing.assert_allclose(kept, plain, rtol=1e-5, atol=1e-6)


def _one_layer(cfg):
    """-> (the layer loop's body, bare; its carry; one layer's leaves)"""
    params = init_params(cfg, jax.random.PRNGKey(0))
    x, rope = transformer._prologue(params, jnp.zeros((B, T), jnp.int32), cfg,
                                    None)
    return (lambda c, lp: transformer._block(c, lp, cfg, rope, None), x,
            jax.tree.map(lambda a: a[0], params["layers"]))


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-moe"])
def test_a_checkpointed_layer_keeps_the_attention_half_and_no_ffn_product(
        preset):
    cfg = dataclasses.replace(get_config(preset), remat=True)
    H, KVH, hd, D = cfg.n_heads, cfg.kv_heads, cfg.hdim, cfg.d_model
    assert cfg.d_ff not in (B, T, H, KVH, hd, D)  # a width of its own
    body, x, lp = _one_layer(cfg)
    kept = [(aval.shape, why) for aval, why in saved_residuals(
                transformer._remat(body, cfg), x, lp)
            if not why.startswith(("from the argument", "from a constant"))]
    assert sorted(shape for shape, _ in kept) == sorted([
        (B, T, H, hd), (B, T, KVH, hd), (B, T, KVH, hd),  # q, k, v, turned
        (B, H, T, hd), (B, H, T),   # the kernel's output and log-sum-exp
        (B, T, D),                  # x + attention: what ln2 reads
    ]), kept
    assert not [shape for shape, _ in kept if cfg.d_ff in shape]
    # without the policy the same layer would keep the FFN's products too
    assert [aval for aval, _ in saved_residuals(body, x, lp)
            if len(aval.shape) >= 3 and aval.shape[-1] == cfg.d_ff]


@pytest.fixture
def flash_forwards_in_grad(monkeypatch):
    """-> cfg -> how often the gradient's jaxpr holds the flash forward (a
    scan holds its body once whatever the depth, so: once a loop that runs
    it). The forward is wrapped in a jit of a name to count."""
    inner = attention._fwd_lse_dispatch

    def dispatch(q, k, v, *static):
        def named_flash_forward(q, k, v):
            return inner(q, k, v, *static)
        return jax.jit(named_flash_forward)(q, k, v)

    monkeypatch.setattr(attention, "_fwd_lse_dispatch", dispatch)

    def walk(jaxpr):
        return sum(
            (eqn.params.get("name") == "named_flash_forward")
            + sum(walk(sub) for sub in jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    def count(cfg):
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = _batch(cfg)
        return walk(jax.make_jaxpr(jax.grad(
            lambda p: loss_fn(p, batch, cfg)[0]))(params).jaxpr)

    return count


@pytest.mark.parametrize("preset", ["tiny-llama", "tiny-moe"])
def test_the_backward_holds_no_second_flash_forward(
        preset, flash_forwards_in_grad, monkeypatch):
    cfg = dataclasses.replace(get_config(preset), remat=True)
    # as many as with everything kept: the backward recomputes none
    kept_all = flash_forwards_in_grad(dataclasses.replace(cfg, remat=False))
    assert kept_all >= 1
    assert flash_forwards_in_grad(cfg) == kept_all
    # what the names buy: a checkpoint that keeps nothing runs it again
    monkeypatch.setattr(transformer, "_KEPT_UNDER_REMAT", ())
    assert flash_forwards_in_grad(cfg) == kept_all + 1
    # and so does one that keeps all but what the rule itself names: the
    # rule's residuals are what the backward kernels read
    monkeypatch.setattr(transformer, "_KEPT_UNDER_REMAT",
                        ("attn_q", "attn_k", "attn_v", "attn_half"))
    assert flash_forwards_in_grad(cfg) == kept_all + 1


def test_ring_attentions_rule_names_nothing():
    """Ring attention calls the lse-returning op once a ring step; naming
    its partials would keep every step's (ops/attention.py)."""
    q = jnp.ones((1, T, 4, 16))
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        attention.flash_attention_with_lse(q, q, q)[0])))(q)
    assert "name[" not in str(jaxpr)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        flash_attention(q, q, q))))(q)
    assert "name[name=flash_out]" in str(jaxpr)
    assert "name[name=flash_lse]" in str(jaxpr)


@pytest.mark.parametrize("differentiated", [False, True])
def test_a_name_outside_a_checkpoint_lowers_to_nothing(differentiated,
                                                       monkeypatch):
    """The serve path's bucket prefill calls the same op undifferentiated,
    and a gradient taken outside any checkpoint meets the names: both lower
    to the text they would have without them."""
    q = jnp.ones((1, T, 4, 16))
    k = jnp.ones((1, T, 2, 16))

    def lowered():
        def op(q, k, v):  # a fresh function: nothing cached across the patch
            if differentiated:
                return jax.grad(lambda q: jnp.sum(flash_attention(q, k, v)))(q)
            return flash_attention(q, k, v)
        return jax.jit(op).lower(q, k, k).as_text()

    named = lowered()
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    # (the lowering numbers its private functions from a counter of the process)
    def unnumbered(text):
        return re.sub(r"(@[A-Za-z_]+?)_\d+\b", r"\1", text)

    assert unnumbered(lowered()) == unnumbered(named)


@pytest.mark.parametrize("differentiated", [False, True])
def test_the_ffns_names_outside_a_checkpoint_lower_to_nothing(differentiated):
    """A gradient of the training layer taken outside any checkpoint meets
    the FFN's names and lowers to the text it has without them, but for the
    numbers in its private functions' names: so the serve path's layers,
    which run `_dense_ffn` too, ask for none and hold none."""
    cfg = get_config("tiny-llama")
    _, x, lp = _one_layer(cfg)

    def op(named):
        def op(x, lp):
            ffn = lambda x: transformer._dense_ffn(x, lp, cfg, named)  # noqa: E731
            if differentiated:
                return jax.grad(lambda x: jnp.sum(ffn(x)))(x)
            return ffn(x)
        return op

    assert str(jax.make_jaxpr(op(True))(x, lp)).count("name[") == 2
    assert "name[" not in str(jax.make_jaxpr(
        lambda x, lp: transformer._ffn_half(x, lp, cfg))(x, lp))

    def unnumbered(text):
        return re.sub(r"(@[A-Za-z_]+?)_\d+\b", r"\1", text)

    assert unnumbered(jax.jit(op(True)).lower(x, lp).as_text()) \
        == unnumbered(jax.jit(op(False)).lower(x, lp).as_text())


# -- the rule ----------------------------------------------------------------

V5E = 16_909_000_000  # `bytes_limit` of one TPU v5e (chip, PR 45)
TODAY = transformer._KEPT_UNDER_REMAT
GATE, UP = transformer._FFN_NAMES


def _cell(**over):
    """The train cell's model: Mistral-7B's widths at 8 layers, bfloat16."""
    return get_config("llama3-8b", **{
        "n_layers": 8, "vocab_size": 32768, "max_seq_len": 8192,
        "dtype": "bfloat16", **over})


# case -> (model, rows x tokens on a device, bytes of a parameter, (limit,
# in use) or None, what is kept of the FFN). The arithmetic, in GB
# (`kept_under_remat`): what is on the device or the parameters + a gradient
# + the stacks of the attention half and the carry + the float32 logits
# twice, then one stack of layers x positions x d_ff a name, under
# 0.9 x 16.909 = 15.218.
RULE_CASES = {
    # 4.07 + 4.03 + 2.42 + 2.15 = 12.67; + 1.88 = 14.55; + 1.88 = 16.43
    "the_cell": (_cell(), 8192, 2, (V5E, 4_070_000_000), (GATE,)),
    # the probe of the benchmark's check: parameters alone on the device
    "the_cell_before_its_state": (_cell(), 8192, 2, (V5E, 0), (GATE,)),
    # 14.5 + 14.5 + 9.7 + 2.1 = 40.8: no depth of 32 fits one chip at all
    "the_cell_at_32_layers": (_cell(n_layers=32), 8192, 2, (V5E, 0), ()),
    # chip_smoke.py's Plan, 2 x 2048 at a vocabulary of 128256:
    # 5.59 + 5.59 + 1.21 + 4.20 = 16.6 (the compiler's total is 12.62 and
    # 13.56 with `gate`: the estimate leans high where logits are large)
    "chip_smokes_plan": (get_config("llama3-8b", n_layers=8), 4096, 2,
                         (V5E, 0), ()),
    # on two chips' worth of memory both stacks fit: 12.63 + 3.76 under 30.4
    "the_cell_on_twice_the_memory": (_cell(), 8192, 2, (2 * V5E, 0),
                                     (GATE, UP)),
    # BASELINE.md's 4 x 2048 with bfloat16 masters: 3.66 + 3.66 + 4.55 +
    # 2.10 = 13.97, and a stack is 2.72 (the compiler's totals: 13.82, and
    # 15.49 with `gate`, which would leave 8% of the limit)
    "llama_2b": (get_config("llama-2b"), 4 * 2048, 2, (V5E, 0), ()),
    # half the rows: 10.65 + 1.36 + 1.36 = 13.36
    "llama_2b_two_rows": (get_config("llama-2b"), 2 * 2048, 2, (V5E, 0),
                          (GATE, UP)),
    # float32 masters: 7.32 + 7.32 alone are 14.6
    "llama_2b_float32": (get_config("llama-2b"), 4 * 2048, 4, (V5E, 0), ()),
    "llama3_8b_whole": (get_config("llama3-8b"), 8192, 2, (V5E, 0), ()),
    "no_memory_reported": (_cell(), 8192, 2, None, ()),
    "experts": (get_config("mixtral-8x7b", n_layers=1), 8192, 2,
                (4 * V5E, 0), ()),
    "no_gate": (get_config("gpt2-125m"), 1024, 2, (4 * V5E, 0), ()),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_rule_keeps_what_fits(case):
    cfg, positions, itemsize, memory, ffn = RULE_CASES[case]
    ask = dict(layers=cfg.n_layers, positions=positions,
               param_itemsize=itemsize, memory=memory)
    names, held = transformer.kept_under_remat(cfg, **ask)
    assert names == TODAY + ffn
    assert (names, held) == transformer.kept_under_remat(cfg, **ask)  # pure
    if memory is not None and not cfg.is_moe and ffn != ():
        stack = cfg.n_layers * positions * cfg.d_ff * 2
        free = memory[0] - held - len(ffn) * stack
        assert free >= transformer._REMAT_MARGIN * memory[0]
        # and one more stack would not leave the margin, or there is none
        assert len(ffn) == 2 or free - stack < transformer._REMAT_MARGIN * memory[0]


def test_the_rules_estimate_leans_a_little_over_the_chips_reading():
    """`memory_peak_bytes` of the train cell on the chip: 12.20 GB with the
    attention half alone (PR 31, set C) and 14.08 GB with `gate` (set D)."""
    cfg, positions, itemsize, memory, _ = RULE_CASES["the_cell"]
    _, held = transformer.kept_under_remat(
        cfg, layers=8, positions=positions, param_itemsize=itemsize,
        memory=memory)
    assert 0 <= held - 12.20e9 < 0.6e9
    assert 0 <= held + 8 * positions * cfg.d_ff * 2 - 14.08e9 < 0.6e9


def _room_for(cfg, params, x, stacks):
    """-> a (limit, in use) under which the rule keeps `stacks` of the FFN's
    names for this loop, and not one more."""
    layers, (B, T, _) = params["layers"]["wq"].shape[0], x.shape
    _, held = transformer.kept_under_remat(
        cfg, layers=layers, positions=B * T, param_itemsize=4, memory=(1, 0))
    stack = layers * B * T * cfg.d_ff * jnp.dtype(cfg.dtype).itemsize
    return (int((held + (stacks + 0.5) * stack)
                / (1 - transformer._REMAT_MARGIN)), 0)


@pytest.mark.parametrize("stacks", [0, 1, 2])
def test_a_checkpointed_dense_layer_keeps_the_products_the_device_has_room_for(
        stacks, monkeypatch):
    cfg = dataclasses.replace(get_config("tiny-llama"), remat=True)
    H, KVH, hd, D, F = cfg.n_heads, cfg.kv_heads, cfg.hdim, cfg.d_model, cfg.d_ff
    params = init_params(cfg, jax.random.PRNGKey(0))
    body, x, lp = _one_layer(cfg)
    monkeypatch.setattr(profiler, "device_memory",
                        lambda devices: _room_for(cfg, params, x, stacks))
    names = transformer._kept_now(cfg, params["layers"], x)
    assert names == TODAY + transformer._FFN_NAMES[:stacks]
    checkpointed = transformer._remat(body, cfg, names)
    kept = [aval.shape for aval, why in saved_residuals(checkpointed, x, lp)
            if not why.startswith(("from the argument", "from a constant"))]
    assert sorted(kept) == sorted([
        (B, T, H, hd), (B, T, KVH, hd), (B, T, KVH, hd),
        (B, H, T, hd), (B, H, T), (B, T, D)] + [(B, T, F)] * stacks), kept

    # products of [B, T, d_ff] in the gradient: gate and up in the forward,
    # the gradient into their gated product, and each of the two AGAIN in
    # the backward unless its name is kept
    def products(jaxpr):
        return sum(
            (eqn.primitive.name == "dot_general"
             and eqn.outvars[0].aval.shape == (B, T, F))
            + sum(products(sub) for sub in jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    grad = jax.make_jaxpr(jax.grad(
        lambda x, lp: jnp.sum(checkpointed(x, lp)[0]), argnums=(0, 1)))(x, lp)
    assert products(grad.jaxpr) == 5 - stacks
    # the gauge says what the loop keeps on a device, name by name
    layers = cfg.n_layers
    for i, name in enumerate(transformer._FFN_NAMES):
        assert profiler._g_remat_kept.get({"name": name}) == (
            layers * B * T * F * 4 if i < stacks else 0)
    assert profiler._g_remat_kept.get({"name": "attn_half"}) == layers * B * T * D * 4


@pytest.mark.parametrize("stacks", [1, 2])
def test_gradients_with_the_ffn_kept_are_those_without(stacks, monkeypatch):
    cfg = dataclasses.replace(get_config("tiny-llama"), n_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, rows=4)
    x = jnp.zeros((4, T, cfg.d_model))
    monkeypatch.setattr(profiler, "device_memory",
                        lambda devices: _room_for(cfg, params, x, stacks))
    got = {remat: jax.jit(jax.grad(lambda p: loss_fn(
        p, batch, dataclasses.replace(cfg, remat=remat))[0]))(params)
        for remat in (True, False)}
    # the loop that was differentiated kept them
    assert profiler._g_remat_kept.get({"name": GATE}) > 0
    assert (profiler._g_remat_kept.get({"name": UP}) > 0) == (stacks == 2)
    flat = jax.tree.leaves(got[False])
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in flat)
    for kept, plain in zip(jax.tree.leaves(got[True]), flat):
        np.testing.assert_allclose(kept, plain, rtol=1e-5, atol=1e-6)


def test_the_decision_is_logged_with_its_numbers(monkeypatch, caplog):
    cfg = dataclasses.replace(get_config("tiny-llama"), remat=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    _, x, _ = _one_layer(cfg)
    room = _room_for(cfg, params, x, 1)
    monkeypatch.setattr(profiler, "device_memory", lambda devices: room)
    logger = get_logger("models.transformer")  # propagates nothing: its own
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, logger=logger.name):
            transformer._kept_now(cfg, params["layers"], x)
    finally:
        logger.removeHandler(caplog.handler)
    (line,) = [r.getMessage() for r in caplog.records]
    assert "ffn_gate" in line and "ffn_up" not in line
    assert "%.3f GB" % (room[0] / 1e9) in line and "10%" in line


# mesh axes -> the pieces a [batch, seq, ..] activation is cut into
SPLITS = {"dp2_fsdp2": (dict(dp=2, fsdp=2), 4), "dp2_sp2": (dict(dp=2, sp=2), 4),
          "tp4": (dict(tp=4), 1), "dp2_pp2": (dict(dp=2, pp=2), 2)}


@pytest.mark.parametrize("case", sorted(SPLITS))
def test_the_rule_counts_the_positions_one_device_holds(case, cpu_mesh_devices):
    axes, ways = SPLITS[case]
    mesh = build_mesh(MeshSpec.create(**axes), devices=cpu_mesh_devices[:4])
    assert split_ways(("batch", "seq"), mesh) == ways
    assert split_ways(("batch", "seq", "mlp"), mesh) == ways * axes.get("tp", 1)
    with no_constrain():  # per-shard code holds the pieces already
        assert split_ways(("batch", "seq"), mesh) == 1


def test_device_memory_is_the_fullest_devices():
    class Device:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    assert profiler.device_memory(jax.local_devices()[:1]) is None  # the CPU
    assert profiler.device_memory([]) is None
    a = Device({"bytes_limit": 100, "bytes_in_use": 10})
    b = Device({"bytes_limit": 100, "bytes_in_use": 60})
    assert profiler.device_memory([a, b]) == (100, 60)
    assert profiler.device_memory([a, Device(None)]) is None
