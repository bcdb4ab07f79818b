"""The LongCat-Flash stack (`mla2`: a double layer of two latent attentions,
two dense FFNs and a shortcut expert layer that holds a share of the
experts beside identity experts) against the plain reference of its family
(benchmark/reference/longcat_flash.py: float32, `highest`, no kernel, no
cache, no absorbed form, nothing imported from the program), on seeded
weights.

Tolerances. Weights are the family's bfloat16 draws cast to float32 and the
tiny model runs in float32, so program and reference differ only in the
order of float32 sums (the absorbed form against the plain one, one-pass
against blockwise softmax, a combine matrix against a scan over experts):
log-probabilities agree to LOGPROB_TOL. The control rounds the same weights
to fp8 and must land far outside it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import longcat_flash as ref
from benchmark.tests.tiny import tiny_spec
from ray_tpu.models import get_config, init_params, stack  # noqa: F401
from ray_tpu.models.transformer import (
    _moe_ffn_dropless_ids,
    _norm,
    moe_rows_computed,
)
from ray_tpu.ops import mla_attention as mla
from ray_tpu.ops import pool_shape
from ray_tpu.ops.attention import flash_attention
from ray_tpu.parallel.moe import sigmoid_bias_gating
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

from engine_programs import PINNED, digest

CONFIG = "longcat-flash-omni"
# float32 on both sides: 2e-5 is over 10x the largest difference seen over
# the cases below (1.6e-6, engine against reference); the fp8 control reads
# 2e-3 rms
LOGPROB_TOL = 2e-5
PAGE = 4


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec(CONFIG)
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(39)))
    # at 64 wide the router's logits spread 0.16 and its scores are nearly
    # flat; 8 x the router gives the choice and the weights something to do
    params["layers"] = [tuple({n: w * (8.0 if n == "router" else 1.0)
                               for n, w in lp.items()} for lp in segment)
                        for segment in params["layers"]]
    cfg = family.model_config(spec, dtype="float32")
    return spec, family, cfg, params


def engine_for(cfg, params, **kw):
    ecfg = dict(max_batch_size=2, page_size=PAGE, max_pages=96, max_seq_len=96,
                prefill_buckets=(8, 16), prefill_chunk=16, decode_span=4,
                busy_span=2, cache_dtype="float32")
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


def reference_logprobs(model, prompt, output, mode=None):
    logits = reference_logits(model, prompt, output, mode)
    top = logits.max(-1, keepdims=True)
    lse = np.log(np.exp(logits - top).sum(-1, keepdims=True)) + top
    return (logits - lse)[np.arange(len(output)), output]


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(3, 256, n).tolist()


def one_layer(model):
    _, _, cfg, params = model
    return cfg, jax.tree.map(lambda a: a[0], params["layers"][0][0])


# -- the stack's shape -------------------------------------------------------


def test_double_layers_are_one_scan_over_one_pool_of_latent_rows(model):
    _, _, cfg, params = model
    assert cfg.layer_kinds == ("mla2",) * 2 and cfg.segments() == ((0, ("mla2",), 2),)
    # two attentions a layer, ONE row a token: 16 + 8 lanes in a 128-lane tile
    assert cfg.cache_dims == (4, 1, 128) and cfg.latent_cache
    assert not cfg.has_state and cfg.counts_choices and cfg.router_width == 12
    lp = params["layers"][0][0]
    assert lp["wq_a0"].shape == lp["wq_a1"].shape == (2, 64, 32)  # [repeats, D, ql]
    assert lp["wk_b1"].shape == lp["wv_b1"].shape == (2, 16, 4, 16)  # [.., kl, H, .]
    assert lp["wkv_a0"].shape == (2, 64, 16) and lp["wkr0"].shape == (2, 64, 8)
    assert lp["router"].shape == (2, 64, 12) and lp["w_in"].shape == (2, 4, 64, 32)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.param_count()
    big = get_config("longcat-flash")
    cut = dataclasses.replace(big, n_layers=4, layer_kinds=("mla2",) * 4,
                              vocab_size=16384)
    # the issue's arithmetic: 4 x (638.9 + 604.0) + 201.3 M
    assert round(cut.param_count() / 1e6, 1) == 5172.7
    assert cut.cache_dims == (8, 1, 640)
    assert moe_rows_computed(cut, 64, 1) == 16 * 64


def test_a_share_of_the_experts_needs_the_dropless_form_and_its_router():
    from ray_tpu.models import StackConfig
    base = dict(name="x", vocab_size=8, d_model=16, n_layers=1, n_heads=4,
                d_ff=16, layer_kinds=("attn",), num_experts=2,
                num_selected_experts=2, n_routed_experts=4)
    with pytest.raises(ValueError, match="renormalises over the chosen"):
        StackConfig(**base, capacity_factor=1.0)
    with pytest.raises(ValueError, match="dropless form alone"):
        StackConfig(**base, router="sigmoid", capacity_factor=0.5)
    with pytest.raises(ValueError, match="lie past the 4 routed"):
        StackConfig(**base, router="sigmoid", capacity_factor=1.0,
                    experts_first=3)
    with pytest.raises(ValueError, match="two shapes of pool rows"):
        StackConfig(**{**base, "n_layers": 2, "layer_kinds": ("mla2", "attn")})


# -- served logits against the reference -------------------------------------


@pytest.mark.parametrize("n_prompt", [5, 21, 40],
                         ids=["bucket", "chunked", "chunks_3"])
def test_served_logprobs_are_the_references(model, engine, n_prompt):
    """Bucket prefill (the plain form), chunked prefill and decode through
    the latent pool (the absorbed form), against one cache-less pass."""
    prompt = prompt_of(n_prompt, n_prompt)
    got = engine.generate(prompt, max_tokens=7)
    want = reference_logprobs(model, prompt, got["token_ids"])
    assert np.abs(np.asarray(got["logprobs"]) - want).max() < LOGPROB_TOL
    control = reference_logprobs(model, prompt, got["token_ids"], "fp8")
    assert np.sqrt(np.mean((np.asarray(got["logprobs"]) - control) ** 2)) \
        > 20 * LOGPROB_TOL


def test_a_prefix_hit_on_latent_pages_gives_the_same_logits(model, engine):
    _, _, cfg, params = model
    shared = prompt_of(36, 7)
    first = engine.generate(shared + [5, 6, 7], max_tokens=5)
    before = common.counters()
    again = engine.generate(shared + [9, 10], max_tokens=5)
    # the shared 36 tokens are nine whole pages: two chunks and a page
    assert common.counter_delta(before, common.counters(),
                                "serve_prefix_cache_hit_tokens") == 36
    cold = engine_for(cfg, params, prefix_caching=False)
    try:
        assert cold.prefix is None
        plain = cold.generate(shared + [9, 10], max_tokens=5)
    finally:
        cold.stop()
    assert again["token_ids"] == plain["token_ids"]
    assert np.abs(np.asarray(again["logprobs"])
                  - np.asarray(plain["logprobs"])).max() < LOGPROB_TOL
    want = reference_logprobs(model, shared + [9, 10], again["token_ids"])
    assert np.abs(np.asarray(again["logprobs"]) - want).max() < LOGPROB_TOL
    assert first["token_ids"] != again["token_ids"]


def test_a_short_question_over_a_cached_prefix_counts_its_padding(model, engine):
    """A re-ask runs ONE chunk, at the cached prefix's end (its ninth page,
    no multiple of a chunk), that holds the question and is padded to
    `prefill_chunk`: the attention is told where the question ends, the
    first token and the logits are the cache-less reference's, and the
    padding is counted beside the chunk's rows."""
    C = engine.ecfg.prefill_chunk
    shared = prompt_of(36, 40)
    engine.generate(shared + [5, 6, 7], max_tokens=2)
    question = [9, 10, 11]
    prompt = shared + question
    before = common.counters()
    again = engine.generate(prompt, max_tokens=5)
    delta = lambda name: common.counter_delta(  # noqa: E731
        before, common.counters(), name)
    assert delta("serve_prefix_cache_hit_tokens") == 36
    assert delta("serve_chunk_rows") == C
    assert delta("serve_chunk_padding_tokens") == C - len(question)
    want = reference_logprobs(model, prompt, again["token_ids"])
    assert np.abs(np.asarray(again["logprobs"]) - want).max() < LOGPROB_TOL
    # the first token is the reference's own choice, not only as likely
    assert again["token_ids"][0] == int(np.argmax(
        reference_logits(model, prompt, again["token_ids"][:1])[0]))


def test_the_devices_counts_of_choices_reach_the_counters(model, engine):
    before = common.counters()
    engine.generate(prompt_of(21, 3), max_tokens=6)
    delta = {k: common.counter_delta(before, common.counters(),
                                     "serve_moe_choices", kind=k)
             for k in ("all", "zero", "held")}
    # 3 choices a token and expert layer, 2 layers; a span's steps past the
    # answer's end are counted as the other counters count them
    assert delta["all"] >= 21 * 3 * 2 and delta["all"] % 6 == 0
    assert 0 < delta["zero"] < delta["all"] and 0 < delta["held"] < delta["all"]
    assert delta["zero"] + delta["held"] <= delta["all"]
    routed = common.counter_delta(before, common.counters(),
                                  "serve_moe_rows_routed")
    assert routed == delta["held"]


# -- latent attention --------------------------------------------------------


def _mla_operands(T, H=4, N=16, R=8, L=16, V=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (T, H, N)), jax.random.normal(ks[1], (T, H, R)),
            jax.random.normal(ks[2], (T, L)), jax.random.normal(ks[3], (T, R)),
            jax.random.normal(ks[4], (L, H, N + V)) * 0.3)


def _split(wkv_b, N=16):
    return wkv_b[..., :N], wkv_b[..., N:]


def test_the_absorbed_form_is_the_plain_form():
    """Queries carried into the latent's space against rows of the pool, and
    every token's keys and values up-projected: the same numbers."""
    T, N, W = 24, 16, 128
    q_n, q_r, c, k_r, wkv_b = _mla_operands(T)
    wk_b, wv_b = _split(wkv_b)
    scale = 24 ** -0.5
    table = jnp.arange(1, 7, dtype=jnp.int32)
    at = jnp.arange(T)

    @jax.jit
    def both():
        kv = jnp.einsum("tl,lhk->thk", c, wkv_b)
        s = (jnp.einsum("qhn,thn->hqt", q_n, kv[..., :N])
             + jnp.einsum("qhr,tr->hqt", q_r, k_r)) * scale
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
        plain = jnp.einsum("hqt,thv->qhv", jax.nn.softmax(s, -1), kv[..., N:])

        def attend(q, pool, layer):
            return mla.latent_attention_chunk(q, pool, table, 0, T, layer, 16,
                                              scale)

        o, pool = mla.write_latent_then_attend(
            attend, stack._absorb(q_n, q_r, wk_b, W),
            stack._latent_row(c, k_r, W),
            jnp.zeros(pool_shape(2, 8, PAGE, 1, W)), 1, table[at // PAGE],
            at % PAGE)
        return plain, stack._unabsorb(o, wv_b), pool

    plain, absorbed, pool = both()
    assert np.abs(absorbed - plain).max() < 2e-5
    assert not np.asarray(pool[0]).any()  # the other layer's rows untouched
    # one more token, decoded over the rows the chunk wrote
    lengths = jnp.asarray([T, 0], jnp.int32)
    q = stack._absorb(q_n[-1:], q_r[-1:], wk_b, W)
    o = mla.latent_attention_decode(
        jnp.concatenate([q, q]), pool, jnp.stack([table, table * 0]), lengths,
        1, 16, scale)
    assert np.abs(stack._unabsorb(o[0], wv_b) - plain[-1]).max() < 2e-5
    assert not np.asarray(o[1]).any()  # a slot with no sequence: zeros


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


def _latent_pool(pages=40, W=256, seed=1):
    k = jax.random.PRNGKey(seed)
    return (jax.random.normal(k, pool_shape(2, pages, 16, 1, W)) * 0.5).astype(
        jnp.bfloat16)


def test_the_decode_kernel_is_its_reference_at_the_gates_edges(interpreted):
    """Interpret mode: lengths 0 (no sequence), 1, a page's edge on either
    side, and more pages than one block holds."""
    H, W, V = 8, 256, 128
    pool = _latent_pool()
    q = (jax.random.normal(jax.random.PRNGKey(2), (6, H, W)) * 0.3).astype(
        jnp.bfloat16)
    assert mla.latent_ok(q, pool, V)
    tables = jnp.asarray(np.random.default_rng(0).permutation(39)[:36]
                         .reshape(6, 6) + 1, jnp.int32)
    lengths = jnp.asarray([0, 1, 16, 17, 95, 96], jnp.int32)
    got = mla.latent_attention_decode(q, pool, tables, lengths, 1, V, 0.1)
    want = mla.latent_attention_decode(q, pool, tables, lengths, 1, V, 0.1,
                                       force_xla=True)
    assert got.shape == (6, H, V) and not np.asarray(got[0], np.float32).any()
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < 2e-2


@pytest.mark.parametrize("start", [0, 48])
def test_the_chunk_kernel_is_its_reference_over_several_tiles(interpreted, start):
    """C x H = 1024 rows are two grid programs' tiles; with a past of 48 the
    second tile's rows see keys the first tile's do not."""
    C, H, W, V = 64, 16, 256, 128
    pool = _latent_pool()
    q = (jax.random.normal(jax.random.PRNGKey(3), (C, H, W)) * 0.3).astype(
        jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(1).permutation(39)[:8] + 1, jnp.int32)
    got = mla.latent_attention_chunk(q, pool, table, start, start + C, 0, V, 0.1)
    want = mla.latent_attention_chunk(q, pool, table, start, start + C, 0, V,
                                      0.1, force_xla=True)
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < 2e-2


@pytest.mark.parametrize("start", [0, 48])
@pytest.mark.parametrize("valid", [1, 8, 9, 37, 64])
def test_a_chunk_pays_for_the_tokens_it_holds(interpreted, start, valid):
    """64 heads make a tile 8 tokens, as at published widths. A chunk of 64
    rows that holds `valid` tokens: their rows are, bit for bit, those of
    the chunk told that all 64 are tokens (same keys, same blocks, a masked
    key adds exactly 0) and the reference's; a tile past the tokens' end
    loops over no page and writes zeros; the padding rows of the tile that
    holds the last token see the real keys and come out finite."""
    C, H, W, V, tile = 64, 64, 256, 128, 8
    assert mla._CHUNK_ROWS // H == tile
    pool = _latent_pool()
    q = (jax.random.normal(jax.random.PRNGKey(3), (C, H, W)) * 0.3).astype(
        jnp.bfloat16)
    table = jnp.asarray(np.random.default_rng(1).permutation(39)[:8] + 1, jnp.int32)

    def chunk(total, **kw):
        return np.asarray(mla.latent_attention_chunk(
            q, pool, table, start, total, 0, V, 0.1, **kw), np.float32)

    got = chunk(start + valid)
    assert np.array_equal(got[:valid], chunk(start + C)[:valid])
    want = chunk(start + valid, force_xla=True)
    assert np.abs(got[:valid] - want[:valid]).max() < 2e-2
    assert not got[-(-valid // tile) * tile:].any()
    assert np.isfinite(got).all() and np.isfinite(want).all()


def test_a_refused_shape_is_said_once_and_takes_the_reference(caplog):
    pool = _latent_pool(W=128)
    q = jnp.zeros((2, 4, 128), jnp.bfloat16)
    mla._refused.clear()
    with caplog.at_level("WARNING", logger=mla.__name__):
        assert not mla.latent_ok(q, pool, 16)
        assert not mla.latent_ok(q, pool, 16)
    assert len([r for r in caplog.records if "no shape of" in r.message]) == 1


def test_flash_attention_takes_keys_of_192_against_values_of_128(interpreted):
    """The plain form's heads: padded with zeros to whole tiles of one
    width, the values' own lanes come back."""
    B, T, H = 1, 128, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k = (jax.random.normal(ks[i], (B, T, H, 192)) * 0.2 for i in (0, 1))
    v = jax.random.normal(ks[2], (B, T, H, 128))
    got = flash_attention(q, k, v, causal=True, scale=192 ** -0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 192 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    assert got.shape == (B, T, H, 128)
    assert np.abs(got - want).max() < 2e-5


# -- the share layer ---------------------------------------------------------


def _share(model, first, held):
    """The layer's weights and both sides' configurations as the chip that
    holds experts first .. first + held of the 8 would have them."""
    spec, family, _, _ = model
    spec = {**spec, "n_routed_experts": held, "held_experts_first": first}
    return spec, family.model_config(spec, dtype="float32")


def _full_layer(model):
    """One layer with all 8 routed experts' weights: the model's 4 and 4
    more made of them (turned round and negated)."""
    spec, cfg = _share(model, 0, 8)
    lp = dict(one_layer(model)[1])
    for n in ("w_in", "w_gate", "w_out"):
        lp[n] = jnp.concatenate([lp[n], -lp[n][::-1]])
    return spec, cfg, lp


def _held(lp, first, held):
    return {n: (w[first:first + held] if n in ("w_in", "w_gate", "w_out") else w)
            for n, w in lp.items()}


def test_the_shares_add_up_to_the_uncut_layer(model):
    """THE share test: the parts that the 2 chips holding 4 of the 8 experts
    each give, the identity experts counted once, are the uncut layer; and
    the program's part is the reference's, share by share."""
    spec8, cfg8, lp = _full_layer(model)
    b = jax.random.normal(jax.random.PRNGKey(6), (1, 10, 64))
    items = ref.static(spec8)
    uncut = ref._experts(b[0], lp, items, None)
    whole, _, ids = _moe_ffn_dropless_ids(b, lp, cfg8)
    assert np.abs(whole[0] - uncut).max() < 2e-6
    parts, chosen = [], 0
    for first in (0, 4):
        spec, cfg = _share(model, first, 4)
        part = ref._experts(b[0], _held(lp, first, 4), ref.static(spec), None,
                            identity=False)
        with_identity = ref._experts(b[0], _held(lp, first, 4),
                                     ref.static(spec), None)
        got, _, ids = _moe_ffn_dropless_ids(b, _held(lp, first, 4), cfg)
        assert np.abs(got[0] - with_identity).max() < 2e-6
        identity = with_identity - part  # the same on every chip
        parts.append(part)
        chosen += int(jnp.sum((ids >= first) & (ids < first + 4)))
    assert np.abs(sum(parts) + identity - uncut).max() < 2e-6
    assert np.abs(identity).max() > 1e-3 and np.abs(parts[0]).max() > 1e-4
    # every choice fell on one chip's experts or on an identity expert
    assert chosen + int(jnp.sum(ids >= 8)) == ids.size


def test_twelve_of_twelve_on_identity_experts_is_the_scaled_input(model):
    """A token whose choices are all zero-compute gets routed_scale x
    sum(score) x b, with the scores the softmax's own (no bias in them)."""
    cfg, lp = one_layer(model)
    lp = dict(lp, router_bias=jnp.where(jnp.arange(12) >= 8, 10.0, 0.0))
    b = jax.random.normal(jax.random.PRNGKey(7), (1, 6, 64))
    out, _, ids = _moe_ffn_dropless_ids(b, lp, cfg)
    assert bool(jnp.all(ids >= 8))
    score = jax.nn.softmax(b @ lp["router"], -1)
    want = 6.0 * jnp.sort(score[..., 8:], -1)[..., 1:].sum(-1, keepdims=True) * b
    assert np.abs(out - want).max() < 2e-6


def test_weights_are_not_renormalised_and_the_bias_moves_the_choice_alone():
    logits = jax.random.normal(jax.random.PRNGKey(8), (16, 12)) * 2
    score = jax.nn.softmax(logits, -1)
    w0, ids0 = sigmoid_bias_gating(logits, jnp.zeros(12), 3, False, 6.0,
                                   softmax_all=True)
    np.testing.assert_allclose(
        w0, 6.0 * jnp.take_along_axis(score, ids0, -1), rtol=1e-6)
    assert float(jnp.abs(w0.sum(-1) - 6.0).max()) > 2.0  # not over their sum
    bias = jnp.zeros(12).at[11].set(5.0)
    w1, ids1 = sigmoid_bias_gating(logits, bias, 3, False, 6.0, softmax_all=True)
    assert bool(jnp.all(jnp.any(ids1 == 11, -1))) and not bool(
        jnp.all(jnp.any(ids0 == 11, -1)))
    np.testing.assert_allclose(
        w1, 6.0 * jnp.take_along_axis(score, ids1, -1), rtol=1e-6)


def test_the_second_attention_does_not_see_the_experts_sum(model):
    """Doubling the experts' output projections adds the held experts' part
    once more to the layer's output and moves nothing else: the shortcut
    joins after the second FFN. (Were it added at the first block, the
    second attention and FFN would turn it.)"""
    cfg, lp = one_layer(model)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 12, 64)) * 0.5
    mode = stack.Seq(cfg)
    mode.at = None

    @jax.jit
    def both(x, lp):
        y, _ = stack._mla2(x, lp, cfg, 0, mode, {})
        bp = stack._block_leaves(lp, 0)
        o, _ = stack._mla(_norm(x, bp["a_ln"], None, cfg), bp, cfg, 0, mode, {})
        b0 = _norm(x + o, bp["p_ln"], None, cfg)
        return y, _moe_ffn_dropless_ids(b0, lp, cfg)[0]

    y1, with_w = both(x, lp)
    y2, _ = both(x, dict(lp, w_out=2 * lp["w_out"]))
    _, without = both(x, dict(lp, w_out=0 * lp["w_out"]))
    assert np.abs((y2 - y1) - (with_w - without)).max() < 2e-6
    assert np.abs(with_w - without).max() > 1e-4


# -- what is not carried over ------------------------------------------------


def test_refusals_name_the_family(model):
    _, _, cfg, params = model
    name = cfg.name
    with pytest.raises(ValueError, match=f"{name}.*pool of latents"):
        engine_for(cfg, params, speculation={"mode": "ngram",
                                             "num_speculative_tokens": 2})
    with pytest.raises(ValueError, match=f"{name}.*no sharding rules"):
        InferenceEngine(params, cfg, EngineConfig(max_pages=8), mesh=object())
    eng = engine_for(cfg, params)
    try:
        with pytest.raises(ValueError, match=f"{name}.*one latent row a token"):
            eng._refuse_kv_transfer("export_kv_pages")
        assert eng.v_pages is None and eng.prefix is not None
    finally:
        eng.stop()
    mode = stack.Verify(cfg, jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1, 4), jnp.int32), PAGE, jnp.zeros((1,), jnp.int32))
    with pytest.raises(NotImplementedError, match=name):
        mode.attend_mla({}, 0, *[None] * 7)


# -- the models that share the expert layer ----------------------------------

# "all held, none zero" must stay the program it was: the share layer's
# fields change nothing for a model that holds every expert
# (tests/engine_programs.py: PINNED, and whose text each is)
@pytest.mark.parametrize("name", ("tiny-lfm2", "tiny-moe"))
def test_all_held_none_zero_lowers_to_the_parents_decode_program(name):
    assert digest(name, "decode") == PINNED[name, "decode"]


# telling the LATENT chunk kernel where a chunk's tokens end (`attend_mla`)
# leaves `attend_full`, and with it every other model's chunk program, the
# text it was
@pytest.mark.parametrize("name", ("tiny-lfm2", "tiny-llama", "tiny-moe"))
def test_the_other_models_chunk_programs_lower_to_the_parents(name):
    assert digest(name, "chunk") == PINNED[name, "chunk"]


# -- the cell, rehearsed -----------------------------------------------------


def test_the_cpu_rehearsal_runs_the_new_cell():
    """`longcat-flash-omni.serve-docs` end to end at the family's tiny cut:
    the benchmark's own drivers, generator, warm-up, window, replay and
    comparison with the plain reference; shared contexts shrunk to the tiny
    engine (benchmark/tests/tiny.py shrinks a mix's prompts and answers and
    leaves `shared_prefix` alone: PERF.md section 7). Prefix hits are served
    and the device's counts of choices come back."""
    import argparse
    import time

    import ray_tpu
    from benchmark import drive
    from benchmark.tests.tiny import tiny_cell

    cell = tiny_cell("longcat-flash-omni.serve-docs")
    cell["traffic"]["shared_prefix"].update(count=3, len=64)
    cell["traffic"]["prompt_len"].update(median=12, min=4, max=30)
    args = argparse.Namespace(seed=2**31 + 39, seconds=1.0, trace=0, sweep="")
    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    try:
        out = drive.measure(cell, args, {"platform": "cpu"},
                            common.CompileWatch(), time.perf_counter())
    finally:
        ray_tpu.shutdown()
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert {"tpot_mean_ms", "setup_s"} <= set(out["end_to_end"])
    delta = lambda name, **tags: common.counter_delta(  # noqa: E731
        *out["counters"], name, **tags)
    assert delta("serve_prefix_cache_hit_tokens") >= 64
    assert 0 < delta("serve_moe_choices", kind="zero") \
        < delta("serve_moe_choices", kind="all")
    assert delta("serve_moe_rows_routed") == delta("serve_moe_choices",
                                                   kind="held")


def test_an_ask_queued_behind_its_contexts_first_ask_takes_its_pages(model):
    """Two asks of one context admitted together: both miss at admission
    (nothing is registered yet), the first prefills, its chunks' pages enter
    the prefix cache as they land, and the second, reaching the head of the
    chunk queue, resumes past them; its log-probabilities are the
    reference's."""
    from ray_tpu.serve.engine import Request

    _, _, cfg, params = model
    shared = prompt_of(48, 11)
    prompts = [shared + [5, 6, 7], shared + [9, 10]]

    def ask(eng):
        reqs = [Request(request_id=f"late{i}", prompt=p, max_tokens=5)
                for i, p in enumerate(prompts)]
        eng._ensure_loop = lambda: None   # stepped by hand: both admitted first
        for r in reqs:
            eng.add_request(r)
        eng._prefill_batch([eng.pending.get() for _ in reqs])
        assert len(eng._chunk_queue) == 2
        for _ in range(200):
            eng._iterate()
            if all(r.done.is_set() for r in reqs):
                break
        assert all(r.done.is_set() and r.error is None for r in reqs)
        return reqs

    before = common.counters()
    eng = engine_for(cfg, params)
    try:
        got = ask(eng)
        for _ in range(4):  # the last spans' pages go at their readback
            eng._iterate()
        # nothing leaked and nothing is still held: every page but the
        # trash page is free or sits in the cache with no ref on it
        assert not eng.prefix.refs
        assert eng.allocator.num_free + len(eng.prefix.by_page) == 96 - 1
        free_after = eng.allocator.num_free
    finally:
        eng.stop()
    # the second ask took the first's three whole chunks (48 tokens)
    assert common.counter_delta(before, common.counters(),
                                "serve_prefix_cache_hit_tokens") == 48
    for r, prompt in zip(got, prompts):
        want = reference_logprobs(model, prompt, r.output)
        assert np.abs(np.asarray(r.output_logprobs) - want).max() < LOGPROB_TOL
    assert free_after > 0


def _admitted(eng, reqs):
    """`reqs` admitted together and stepped by hand (no loop thread)."""
    eng._ensure_loop = lambda: None
    for r in reqs:
        eng.add_request(r)
    eng._prefill_batch([eng.pending.get() for _ in reqs])
    return reqs


def _run_out(eng, reqs, turns=300):
    for _ in range(turns):
        eng._iterate()
        if all(r.done.is_set() for r in reqs):
            break
    for _ in range(4):  # the last spans' pages go at their readback
        eng._iterate()
    assert all(r.done.is_set() for r in reqs)


def _nothing_leaked(eng, pages=96):
    assert not eng.prefix.refs
    assert eng.allocator.num_free + len(eng.prefix.by_page) == pages - 1


def test_a_deep_chunk_queue_takes_turns_with_the_decoders_chunk_for_step(model):
    """One prompt in the chunk queue advances a chunk an iteration, a wide
    one (32 rows: the model has routed experts) while more than a chunk of
    it is left; three advance `busy_span` (2 here) chunks of 16 rows an
    iteration at the most, a wide one or two of 16, the oldest prompt's
    first, a last chunk and another prompt's first in one turn among them;
    every answer's log-probabilities are the reference's."""
    from ray_tpu.serve.engine import Request

    _, _, cfg, params = model
    prompts = [prompt_of(40, 21), prompt_of(37, 22), prompt_of(52, 23)]
    eng = engine_for(cfg, params, max_batch_size=4)
    turns = []
    one = eng._advance_chunk

    def counted(room=None):
        ran = one(room)
        turns[-1] += bool(ran)
        return ran

    eng._advance_chunk = counted
    try:
        first = _admitted(eng, [Request(request_id="alone",
                                        prompt=prompt_of(40, 20),
                                        max_tokens=40)])
        while eng._chunk_queue:
            turns.append(0)
            eng._iterate()
        assert turns == [1, 1]  # 40 tokens: a chunk of 32 and one of 16
        del turns[:]
        rest = _admitted(eng, [
            Request(request_id=f"deep{i}", prompt=p, max_tokens=6)
            for i, p in enumerate(prompts)])
        while eng._chunk_queue:
            turns.append(0)
            eng._iterate()
        # 40, 37 and 52 tokens: a wide chunk is a turn of two; the second
        # turn is one prompt's last chunk and the next one's first, of 16
        # rows each; one chunk a turn for the last prompt alone
        assert turns == [1, 2, 1, 1, 1]
        assert eng._active()  # the first ask decoded between the turns
        _run_out(eng, first + rest)
        _nothing_leaked(eng)
    finally:
        eng.stop()
    for r in first + rest:
        assert r.error is None
        want = reference_logprobs(model, r.prompt, r.output)
        assert np.abs(np.asarray(r.output_logprobs) - want).max() < LOGPROB_TOL


@pytest.mark.parametrize("pressure", [False, True],
                         ids=["the_follower_resumes", "evicted_before_it_resumes"])
def test_a_first_ask_cancelled_mid_prefill_leaves_sound_pages(model, pressure):
    """A first ask is cancelled after two of its chunks, whose pages are in
    the prefix cache already. A follower of the same context, queued behind
    it, takes them (the programs that wrote them were dispatched, so they
    hold what the prefix says), holds them against eviction by its refs,
    and answers as the reference does. Under pressure another prompt's
    pages evict them first, once the cancelled ask's refs are gone and
    before the follower has looked: it then finds nothing, prefills its
    whole prompt itself, and answers the same. No page leaks either way."""
    from ray_tpu.serve.engine import Request

    _, _, cfg, params = model
    shared = prompt_of(48, 31)
    a = Request(request_id="a", prompt=shared + [5, 6, 7], max_tokens=5)
    b = Request(request_id="b", prompt=shared + [9, 10], max_tokens=5)
    c = Request(request_id="c", prompt=prompt_of(50, 32), max_tokens=5)
    # 14 pages each; under pressure 30 usable: with a's and b's out and a's
    # six uncached ones back, c's 14 need six of a's eight cached pages
    pages = 31 if pressure else 96
    eng = engine_for(cfg, params, max_pages=pages, busy_span=1)
    before = common.counters()
    try:
        _admitted(eng, [a, b])
        eng._iterate()
        eng._iterate()  # a's first two chunks: 32 tokens, 8 pages registered
        assert len(eng.prefix.by_page) == 8 and not a.done.is_set()
        landed = list(eng.prefix.by_page)
        eng.cancel("a")
        eng._iterate()  # the cancel lands; no chunk of b's has run yet
        assert a.done.is_set() and len(eng.prefix.lru) == 8
        if pressure:
            _admitted(eng, [c])
            assert len(eng.prefix.by_page) == 2  # the chain's head is gone
        eng._iterate()  # b's turn: it looks the context up again
        assert len(eng.prefix.lru) == (2 if pressure else 0)
        assert pressure or all(eng.prefix.refs[p] == 1 for p in landed)
        asked = [a, b] + ([c] if pressure else [])
        _run_out(eng, asked)
        _nothing_leaked(eng, pages)
    finally:
        eng.stop()
    assert a.finish_reason == "cancelled"
    hit = common.counter_delta(before, common.counters(),
                               "serve_prefix_cache_hit_tokens")
    assert hit == (0 if pressure else 32)
    for r in asked[1:]:
        assert r.error is None
        want = reference_logprobs(model, r.prompt, r.output)
        assert np.abs(np.asarray(r.output_logprobs) - want).max() < LOGPROB_TOL
