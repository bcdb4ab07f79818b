"""The Solar Open 2 stack (the family's tiny cut: gated GQA without positions
FIRST of its period and then three layers of Kimi Delta Attention, a delta
rule whose decay is a vector over the key channels; every layer's second half
a SHARE of the routed experts beside a shared expert) against the plain
reference of its family (benchmark/reference/solar_open2.py: float32,
`highest`, no kernel, no cache, the recurrence a token-by-token scan, nothing
imported from the program), on seeded weights; and the two delta-rule ops
(ops/gdn.py) in their channel form, XLA path and Pallas kernels in interpret
mode, against the plain recurrence.

Tolerances. Weights are the family's bfloat16 draws cast to float32 and the
tiny model runs in float32, so program and reference differ only in the order
of float32 sums: logits and log-probabilities agree to LOGPROB_TOL, the ops
to OP_TOL (CHUNK_TOL for the kernel's WY form, whose triangular solve
amplifies rounding where beta is 2). The controls (a bfloat16 state, a
bfloat16 router, int8 weights) must land far outside LOGPROB_TOL."""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from benchmark.reference import solar_open2 as ref
from benchmark.tests.tiny import tiny_spec
from ray_tpu.models import StackConfig, forward, stack
from ray_tpu.models.transformer import _moe_ffn_dropless_ids, _shared_experts
from ray_tpu.ops import gdn
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

# float32 on both sides: the largest differences seen over the cases below
# are 1.4e-6 (a forward pass of 256 positions) and 2.1e-6 (engine against
# reference, a chunked prompt of 48 and 30 decoded tokens); the bfloat16
# state control reads 1.5e-3 rms, the bfloat16 router 1.4e-3 and the int8
# weight control 6e-3
LOGPROB_TOL = 2e-5
OP_TOL = 5e-6
CHUNK_TOL = 2e-5
PAGE = 4
CONFIG = "solar-open2-250b"
CELL = CONFIG + ".serve-mixedlen"


@pytest.fixture(scope="module")
def model():
    spec = tiny_spec(CONFIG)
    family = common.family(spec)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: family.init_weights(spec, k))(jax.random.PRNGKey(52)))
    cfg = family.model_config(spec, dtype="float32")
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
    padded = np.zeros((-(-len(seq) // ref.Q_BLOCK) * ref.Q_BLOCK,), np.int32)
    padded[:len(seq)] = seq
    at = len(prompt) - 1 + np.arange(len(output))
    logits = np.asarray(family.logits_at(params, jnp.asarray(padded),
                                         jnp.asarray(at), spec, mode), np.float64)
    return logits - np.log(np.exp(logits - logits.max(-1, keepdims=True))
                           .sum(-1, keepdims=True)) - logits.max(-1, keepdims=True)


def prompts(n, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 256, t).tolist() for t in lengths[:n]]


# -- the stack's shape -------------------------------------------------------


def test_a_gated_attention_layer_leads_each_period_of_channel_decay_layers(model):
    spec, family, cfg, params = model
    assert cfg.layer_kinds == ("attn", "gdn", "gdn", "gdn") * 2
    assert cfg.segments() == ((0, ("attn", "gdn", "gdn", "gdn"), 2),)
    assert cfg.cache_dims == (2, 2, 16) and cfg.gdn_dims == (6, 4, 8, 8)
    assert cfg.conv_tail == (6, 3, 4 * 3 * 8) and cfg.conv_taps == 4
    assert cfg.gdn_channel_rank == cfg.gdn_gate_rank == 8 and cfg.attn_gate
    assert cfg.positional == "none" and not cfg.post_norm and cfg.has_state
    # a share of the experts beside a shared expert: 4 of 16 held, top 3
    assert (cfg.num_experts, cfg.experts_routed, cfg.router_width) == (4, 16, 16)
    assert cfg.counts_choices and cfg.d_ff_shared == 32 and cfg.norm_topk
    assert cfg.second_halves == ("moe",) * 8
    (period,) = params["layers"]
    gate, kda = period[0], period[1]
    assert gate["wg"].shape == (2, 64, 4, 16) and "d_in" not in gate
    assert kda["d_fa"].shape == (2, 64, 8) and kda["d_fb"].shape == (2, 8, 32)
    assert kda["d_dt_b"].shape == (2, 32) and kda["d_A_log"].shape == (2, 4)
    assert kda["d_b"].shape == (2, 64, 4) and kda["d_gb_b"].shape == (2, 32)
    assert "d_ab" not in kda and "d_gate" not in kda
    assert kda["router"].shape == (2, 64, 16) and kda["w_in"].shape == (2, 4, 64, 32)
    assert kda["sh_in"].shape == (2, 64, 32)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.param_count()
    tree = stack.init_params(cfg, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == cfg.param_count()
    big = family.model_config(common.load_json("configs", CONFIG + ".json"))
    assert big.segments() == ((0, ("attn", "gdn", "gdn", "gdn"), 2),)
    assert round(big.param_count() / 1e9, 2) == 3.90     # the issue's count
    assert big.conv_tail == (6, 3, 24576) and big.cache_dims == (2, 8, 128)
    assert gdn.state_shape(*big.gdn_dims[:1], 64, *big.gdn_dims[1:]) == (
        6, 64, 128, 64 * 128)


def test_the_new_fields_belong_to_their_kinds():
    base = dict(name="x", vocab_size=8, d_model=16, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=16)
    with pytest.raises(ValueError, match="gdn kind's"):
        StackConfig(**base, layer_kinds=("attn", "attn"), gdn_channel_rank=4)
    with pytest.raises(ValueError, match="gdn kind's"):
        StackConfig(**base, layer_kinds=("attn", "attn"), gdn_gate_rank=4)
    with pytest.raises(ValueError, match="attn and swa kinds'"):
        StackConfig(**base, layer_kinds=("conv", "conv"), attn_gate=True)
    cfg = StackConfig(**base, layer_kinds=("attn", "gdn"), gdn_heads=2,
                      gdn_key_dim=8, gdn_value_dim=8, gdn_channel_rank=4,
                      attn_gate=True)
    # the scalar form's leaves where the rank is 0, the channel form's else
    assert {"d_fa", "d_fb", "d_b", "d_gate"} <= set(stack.layer_shapes(cfg, "gdn"))
    assert "d_ab" not in stack.layer_shapes(cfg, "gdn")
    assert "wg" in stack.layer_shapes(cfg, "attn")


def test_what_is_still_not_written_stays_refused_by_name():
    """Shared experts beside a share layer run (the tests below); beside a
    router that reads the layer's input, or inside an `mla2` layer, they do
    not, and say so."""
    base = dict(name="x", vocab_size=8, d_model=16, n_layers=1, n_heads=4,
                d_ff=16, num_experts=2, num_selected_experts=2,
                n_routed_experts=4, router="sigmoid", capacity_factor=1.0)
    StackConfig(**base, layer_kinds=("attn",), d_ff_shared=8)
    with pytest.raises(ValueError, match='router_input="layer"'):
        StackConfig(**base, layer_kinds=("attn",), d_ff_shared=8,
                    router_input="layer")
    with pytest.raises(ValueError, match="renormalises over the chosen"):
        StackConfig(**{**base, "router": "softmax"}, layer_kinds=("attn",),
                    d_ff_shared=8)
    with pytest.raises(ValueError, match="dropless form alone"):
        StackConfig(**{**base, "capacity_factor": 0.5}, layer_kinds=("attn",),
                    d_ff_shared=8)


# -- the two ops in their channel form against the plain recurrence ----------


def _operands(B, T, H, dk, dv, seed=0, decay=0.3, beta=None):
    """q, k normalised as the mixer hands them over; g <= 0 a KEY CHANNEL;
    beta in (0, 2) or fixed; a carried state that is not zero."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = -decay * jnp.exp(jax.random.normal(ks[3], (B, T, H, dk)))
    b = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    if beta is not None:
        b = jnp.full_like(b, beta)
    s0 = jax.random.normal(ks[5], (B, dk, H * dv))
    return q, k, v, g, b, s0


def _plain(q, k, v, g, beta, s0):
    """The reference's own scan in float64, one sequence at a time, from
    s0; g [B,T,H,dk] or [B,T,H]."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    outs, ends = [], []
    for b in range(B):
        S = f(s0[b]).reshape(dk, H, dv).transpose(1, 0, 2)
        o = np.zeros((T, H, dv))
        for t in range(T):
            a = np.exp(f(g[b, t]))
            S = (a[:, :, None] if a.ndim == 2 else a[:, None, None]) * S
            kt = f(k[b, t])
            d = f(beta[b, t])[:, None] * (
                f(v[b, t]) - np.einsum("hij,hi->hj", S, kt))
            S = S + kt[:, :, None] * d[:, None, :]
            o[t] = np.einsum("hij,hi->hj", S, f(q[b, t]))
        outs.append(o)
        ends.append(S.transpose(1, 0, 2).reshape(dk, H * dv))
    return np.stack(outs), np.stack(ends)


@pytest.fixture
def pallas_everywhere(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FORCE_PALLAS", "1")


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("case", ["ragged", "decay_20_a_step", "beta_0",
                                  "beta_2", "published_head"])
def test_the_channel_chunk_op_is_the_plain_recurrence(request, path, case):
    """Lengths that are no multiple of the kernel's block of 64 (the rest is
    padding: g = beta = 0 there) from a carried state that is not zero; a
    decay of e^-20 a position in EVERY channel over whole blocks (the
    factoring k exp(G) . k exp(-G) would overflow float32 after 5
    positions: the kernel's exponents stay <= 0); beta at 0 (nothing is
    written) and at 2 (a step's eigenvalue is -1); and the published head,
    128 key channels against 128 values."""
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    B, T, H, dk, dv = (1, 64, 2, 128, 128) if case == "published_head" \
        else (2, 128, 2, 16, 32)
    q, k, v, g, beta, s0 = _operands(
        B, T, H, dk, dv, seed=3, decay=0.05 if case == "beta_2" else 0.3,
        beta={"beta_0": 0.0, "beta_2": 2.0}.get(case))
    if case == "decay_20_a_step":
        g = jnp.full_like(g, -20.0)
    n = np.array([T - 37, T][:B]) if case == "ragged" else np.full((B,), T)
    valid = jnp.asarray(np.arange(T)[None, :] < n[:, None])[..., None]
    g, beta = jnp.where(valid[..., None], g, 0.0), jnp.where(valid, beta, 0.0)
    run = jax.jit(lambda *a: gdn.gdn_chunk(*a, force_xla=path == "xla"))
    assert ("pallas_call" in str(jax.make_jaxpr(run)(q, k, v, g, beta, s0))) \
        == (path == "pallas")
    o, s1 = run(q, k, v, g, beta, s0)
    want_o, want_s = _plain(q, k, v, g, beta, s0)
    mask = np.asarray(valid)[..., None]
    tol = CHUNK_TOL if path == "pallas" else OP_TOL
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.where(mask, np.asarray(o) - want_o, 0)).max() < tol
    # the padded positions left the state as the last real one did
    assert np.abs(np.asarray(s1) - want_s).max() < tol
    if case == "beta_0":  # nothing written: the state only decays
        assert np.abs(np.asarray(s1)).max() < np.abs(np.asarray(s0)).max()


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_block_of_padding_leaves_the_state_bit_for_bit(request, path):
    """g = 0 and beta = 0 at every position of a call: a dead chunk."""
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    q, k, v, g, beta, s0 = _operands(1, 64, 2, 16, 32, seed=4)
    _, s1 = gdn.gdn_chunk(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), s0,
                          force_xla=path == "xla")
    assert (np.asarray(s1) == np.asarray(s0)).all()


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_a_channel_prompt_fed_in_chunks_equals_the_same_prompt_whole(
        request, path):
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    q, k, v, g, beta, s0 = _operands(1, 192, 2, 16, 32, seed=5)
    force = path == "xla"
    whole_o, whole_s = gdn.gdn_chunk(q, k, v, g, beta, s0, force_xla=force)
    s, outs = s0, []
    for a in range(0, 192, 64):
        o, s = gdn.gdn_chunk(*(x[:, a:a + 64] for x in (q, k, v, g, beta)), s,
                             force_xla=force)
        outs.append(o)
    assert np.abs(np.asarray(jnp.concatenate(outs, 1) - whole_o)).max() < CHUNK_TOL
    assert np.abs(np.asarray(s - whole_s)).max() < CHUNK_TOL


@pytest.mark.parametrize("path", ["xla", "pallas"])
@pytest.mark.parametrize("live", [(True, False, True, False), (False,) * 4,
                                  (False, False, True, True), (True,) * 4])
def test_a_channel_step_advances_live_slots_and_leaves_the_others_bit_for_bit(
        request, path, live):
    """One layer of the whole state array, in place, the decay a [dk, H]
    operand beside k and q; an empty slot's program moves nothing."""
    if path == "pallas":
        request.getfixturevalue("pallas_everywhere")
    B, H, dk, dv = 4, 2, 16, 64
    q, k, v, g, beta, s0 = _operands(B, 1, H, dk, dv, seed=7)
    state = jnp.stack([s0 * 0.5, s0, s0 * 2.0])
    lv = jnp.asarray(live)
    run = jax.jit(lambda st, *a: gdn.gdn_step(st, 1, *a,
                                              force_xla=path == "xla"))
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], lv)
    assert ("pallas_call" in str(jax.make_jaxpr(run)(state, *args))) \
        == (path == "pallas")
    o, new = run(state, *args)
    want_o, want_s = _plain(q, k, v, g, beta, s0)
    on = np.asarray(live)
    assert np.abs(np.asarray(o)[on] - want_o[on, 0]).max(initial=0) < OP_TOL
    assert np.abs(np.asarray(new[1])[on] - want_s[on]).max(initial=0) < OP_TOL
    assert (np.asarray(new[1])[~on] == np.asarray(state[1])[~on]).all()
    assert (np.asarray(new[0]) == np.asarray(state[0])).all()
    assert (np.asarray(new[2]) == np.asarray(state[2])).all()


def _scalar_step_as_it_was(S, q, k, v, alpha, beta):
    """ops/gdn.py `_one_step` as PR 51 left it: one decay a head."""
    S = S * alpha[:, None, :, None]
    d = beta[..., None] * (v - jnp.einsum("bihj,bhi->bhj", S, k))
    S = S + jnp.einsum("bhi,bhj->bihj", k, d)
    return S, jnp.einsum("bihj,bhi->bhj", S, q)


@pytest.mark.parametrize("op", ["chunk", "step"])
def test_the_scalar_form_is_bit_for_bit_what_it_was(op):
    """One decay a head takes the expressions it always took (the kernels'
    text is pinned in tests/test_tpu_compile.py); a channel decay that is
    the same number in every channel is that recurrence too."""
    B, T, H, dk, dv = 2, 24, 2, 8, 16
    q, k, v, g4, beta, s0 = _operands(B, T, H, dk, dv, seed=9)
    g = g4[..., 0]
    same = jnp.broadcast_to(g[..., None], g4.shape)
    with jax.default_matmul_precision("highest"):
        if op == "chunk":
            def was(S, xs):
                q_t, k_t, v_t, g_t, b_t = xs
                return _scalar_step_as_it_was(S, q_t, k_t, v_t, jnp.exp(g_t), b_t)
            s1, o = jax.lax.scan(was, s0.reshape(B, dk, H, dv), tuple(
                jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
            want = jnp.moveaxis(o, 0, 1), s1.reshape(B, dk, H * dv)
            got = gdn.gdn_chunk(q, k, v, g, beta, s0, force_xla=True)
            wide = gdn.gdn_chunk(q, k, v, same, beta, s0, force_xla=True)
        else:
            live = jnp.array([True, False])
            state = jnp.stack([s0, s0 * 2.0])
            new, o = _scalar_step_as_it_was(
                state[1].reshape(B, dk, H, dv), q[:, 0], k[:, 0], v[:, 0],
                jnp.exp(g[:, 0]), beta[:, 0])
            new = jnp.where(live[:, None, None], new.reshape(B, dk, H * dv),
                            state[1])
            want = o, state.at[1].set(new)
            args = (q[:, 0], k[:, 0], v[:, 0])
            got = gdn.gdn_step(state, 1, *args, g[:, 0], beta[:, 0], live,
                               force_xla=True)
            wide = gdn.gdn_step(state, 1, *args, same[:, 0], beta[:, 0], live,
                                force_xla=True)
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()
    for a, b in zip(wide, want):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < OP_TOL


# -- the mixers over the modes -----------------------------------------------


@pytest.mark.parametrize("path", ["seq", "seq_then_decode", "chunks"])
def test_the_channel_decay_mixer_equals_the_whole_sequence(model, path):
    """One sequence of 23 positions through the KDA mixer: whole (`Seq`); 9
    positions kept (`Seq` with `keep`, padded to 16) and then 14 `Decode`
    steps from the tail and the state matrix; three chunks of 8 from carried
    state, the last with 7 real positions. Row 4 of 6."""
    spec, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][0][1])
    plain = {n: w for n, w in lp.items() if n.startswith("d_")}
    T, gi = 23, 4
    u = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.kda(u[0], plain, dict(ref.static(spec)))
    state = stack.new_request_state(cfg, 1, jnp.float32)
    tables = jnp.ones((1, 4), jnp.int32)  # page 1: a live slot
    if path == "seq":
        got, _ = stack._gdn(u, lp, cfg, gi, stack.Seq(cfg), {})
    elif path == "seq_then_decode":
        n, pad = 9, 16
        head = jnp.zeros((1, pad, cfg.d_model)).at[:, :n].set(u[:, :n])
        mode = stack.Seq(cfg, n_valid=jnp.array([n]), keep=True)
        first, carry = stack._gdn(head, lp, cfg, gi, mode, dict(state))
        outs = [first[:, :n]]
        for t in range(n, T):
            mode = stack.Decode(cfg, jnp.array([t]), tables, PAGE)
            o, carry = stack._gdn(u[:, t:t + 1], lp, cfg, gi, mode, carry)
            outs.append(o)
        got = jnp.concatenate(outs, axis=1)
    else:
        C, carry, outs = 8, dict(state), []
        for start in range(0, T, C):
            n = min(C, T - start)
            chunk = jnp.zeros((1, C, cfg.d_model)).at[:, :n].set(
                u[:, start:start + n])
            mode = stack.Seq(cfg, n_valid=jnp.array([n]), keep=True,
                             chunk=(start, tables[0]), page_size=PAGE)
            o, carry = stack._gdn(chunk, lp, cfg, gi, mode, carry)
            outs.append(o[:, :n])
        got = jnp.concatenate(outs, axis=1)
        assert not np.asarray(carry["gdn"][:gi]).any()
        assert np.asarray(carry["gdn"][gi]).any()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=OP_TOL, rtol=0)


def test_the_attention_gate_is_a_sigmoid_of_the_mixers_input_lane_by_lane(model):
    spec, _, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][0][0])
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 12, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.gqa(u[0], lp, dict(ref.static(spec)))
        mode = stack.Seq(cfg)
        mode.embed({"embed": jnp.zeros((256, cfg.d_model))},
                   jnp.zeros((1, 12), jnp.int32))
        got, _ = stack._attn(u, lp, cfg, 0, mode, {})
        bare, _ = stack._attn(u, lp, dataclasses.replace(cfg, attn_gate=False),
                              0, mode, {})
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=OP_TOL, rtol=0)
    assert np.abs(np.asarray(bare[0]) - np.asarray(want)).max() > 100 * OP_TOL


# -- a share of the experts beside a shared expert ---------------------------


def _uncut(model):
    """One expert layer with ALL 16 experts held and its shared expert
    (drawn for the purpose, large enough to be seen beside the tolerance),
    under the tiny model's router."""
    spec, family, cfg, params = model
    lp = jax.tree.map(lambda a: a[0], params["layers"][0][1])
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    lp = dict(lp, w_in=jax.random.normal(ks[0], (16, 64, 32)) * 0.1,
              w_gate=jax.random.normal(ks[1], (16, 64, 32)) * 0.1,
              w_out=jax.random.normal(ks[2], (16, 32, 64)) * 0.1,
              sh_in=jax.random.normal(ks[3], (64, 32)) * 0.1,
              sh_gate=jax.random.normal(ks[4], (64, 32)) * 0.1,
              sh_out=jax.random.normal(ks[5], (32, 64)) * 0.1)
    half = {n: lp[n] for n in ("w_in", "w_gate", "w_out", "router",
                               "router_bias", "sh_in", "sh_gate", "sh_out")}
    return half


def _share(model, half, first, held):
    spec, family, cfg, _ = model
    spec = dict(spec, n_routed_experts=held, held_experts_first=first)
    cfg = family.model_config(spec, dtype="float32")
    part = dict(half, **{n: half[n][first:first + held]
                         for n in ("w_in", "w_gate", "w_out")})
    return spec, cfg, part


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(model):
    """THE share test: the routed parts that the 4 chips holding 4 of the 16
    experts each give, plus the shared expert counted ONCE, are the uncut
    layer; the weights are renormalised over ALL the chosen, held or not;
    and the program's part is the reference's, share by share."""
    half = _uncut(model)
    b = jax.random.normal(jax.random.PRNGKey(6), (1, 10, 64))
    spec16, cfg16, _ = _share(model, half, 0, 16)
    assert not cfg16.counts_choices
    with jax.default_matmul_precision("highest"):
        uncut = ref._experts(b[0], half, ref.static(spec16), None)
        shared = np.asarray(_shared_experts(b, half, cfg16)[0])
        parts, chosen = [], 0
        for first in (0, 4, 8, 12):
            spec, cfg, part = _share(model, half, first, 4)
            assert cfg.counts_choices and cfg.experts_first == first
            routed = ref._experts(b[0], part, ref.static(spec), None,
                                  shared=False)
            whole = ref._experts(b[0], part, ref.static(spec), None)
            got, _, ids = _moe_ffn_dropless_ids(b, part, cfg)
            # the program's routed part, and with its shared expert the
            # whole of what this chip computes
            assert np.abs(got[0] - routed).max() < 2e-6
            assert np.abs(got[0] + shared - whole).max() < 2e-6
            parts.append(np.asarray(routed))
            chosen += int(jnp.sum((ids >= first) & (ids < first + 4)))
    assert np.abs(sum(parts) + shared - uncut).max() < 2e-6
    assert np.abs(shared).max() > 1e-3 and np.abs(parts[0]).max() > 1e-4
    assert chosen == ids.size  # every choice fell on one chip's experts
    # the weights of a token's three choices sum to routed_scale = 1 over
    # the chips, whichever of them hold the experts
    weights, _ = ref.route(b[0], {k: half[k] for k in ("router", "router_bias")},
                           dict(ref.static(spec16)))
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)


def _output_loads(model, tokens, zero_bias=False):
    """[layers, outputs]: how often each of the router's outputs is among a
    token's choices, over `tokens` passed through the plain reference, as a
    multiple of the even share."""
    spec, _, _, params = model
    items, rows, l = ref.static(spec), [], 0
    E, k = spec["n_routed_experts_total"], spec["num_experts_per_tok"]
    x = params["embed"][tokens]
    for segment in params["layers"]:
        for rep in range(jax.tree.leaves(segment)[0].shape[0]):
            for stacked in segment:
                lp = jax.tree.map(lambda a: a[rep], stacked)
                if zero_bias:
                    lp["router_bias"] = jnp.zeros_like(lp["router_bias"])
                x, b = ref._mix(x, lp, kind=ref.kind_of(l, spec), items=items,
                                mode=None)
                with jax.default_matmul_precision("highest"):
                    _, ids = ref.route(b, lp, dict(items))
                rows.append(np.bincount(np.asarray(ids).ravel(), minlength=E)
                            / (ids.size / E))
                half = {n: lp[n] for n in (*ref.EXPERTS, "router",
                                           "router_bias", "sh_in", "sh_gate",
                                           "sh_out")}
                x = x + ref._experts(b, half, items=items, mode=None)
                l += 1
    return np.array(rows)


@pytest.mark.parametrize("skew", [0.3, 1.0])
def test_a_balanced_bias_spreads_the_choices_evenly(skew):
    """`balanced_bias` on scores whose outputs differ in popularity by a
    persistent offset (what a random router sees of a stream that is not
    isotropic): with it every output is chosen within 2% of the even share
    on the sample and within sampling noise on other tokens; without it the
    popular outputs take several times their share."""
    family = common.family(tiny_spec(CONFIG))
    E, N, k = 64, 8192, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    offset = skew * jax.random.normal(ks[0], (E,))
    sample, other = (jax.nn.sigmoid(1.28 * jax.random.normal(kk, (N, E)) + offset)
                     for kk in ks[1:])
    bias = jax.jit(lambda s: family.balanced_bias(s, k))(sample)
    assert abs(float(bias.mean())) < 1e-6

    def loads(score, bias):
        _, ids = jax.lax.top_k(score + bias, k)
        return np.bincount(np.asarray(ids).ravel(), minlength=E) / (N * k / E)

    assert np.abs(loads(sample, bias) - 1).max() < 0.02
    assert np.abs(loads(other, bias) - 1).max() < 0.2
    assert np.abs(loads(other, bias)[:8].mean() - 1) < 0.05
    assert np.abs(loads(sample, 0.0) - 1).max() > 0.5


def test_the_familys_router_is_balanced_over_all_its_outputs(model):
    """`init_weights` leaves every layer a bias under which FRESH random
    tokens choose each of the 16 outputs, and so the 4 held here, about
    equally often; the same weights under a zero bias do not."""
    spec, _, _, params = model
    held = spec["n_routed_experts"]
    tokens = jax.random.randint(jax.random.PRNGKey(9), (512,), 0,
                                spec["vocab_size"])
    for segment in params["layers"]:
        for stacked in segment:
            bias = np.asarray(stacked["router_bias"])
            assert (np.abs(bias).max(-1) > 0).all()
            assert np.abs(bias.mean(-1)).max() < 1e-3
    even, drawn = _output_loads(model, tokens), _output_loads(model, tokens, True)
    assert even.std() < 0.6 * drawn.std()
    assert np.abs(even[:, :held].mean() - 1) < 0.03
    assert np.abs(even[:, :held].mean(1) - 1).max() < 0.12


def test_the_serve_paths_forms_agree_on_a_share_beside_a_shared_expert(model):
    """The forms the engine's programs take (a step that visits the chosen
    experts, a chunk that groups rows by expert) and the dropless form give
    one answer for a layer that holds a share and a shared expert: the whole
    model decoded through the engine is the plain forward's tokens."""
    _, _, cfg, params = model
    prompt = prompts(1, [21])[0]
    eng = engine_for(cfg, params)
    try:
        got = eng.generate(prompt, max_tokens=12)
    finally:
        eng.stop()
    seq = list(prompt)
    for _ in range(12):
        logits, _ = forward(params, jnp.asarray(seq, jnp.int32)[None], cfg)
        seq.append(int(jnp.argmax(logits[0, -1])))
    assert got["token_ids"] == seq[len(prompt):]


# -- the whole model ---------------------------------------------------------


def test_forward_agrees_with_the_plain_reference(model):
    spec, family, cfg, params = model
    tokens = np.asarray(prompts(1, [ref.Q_BLOCK])[0], np.int32)
    got, _ = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens[None])
    at = np.arange(len(tokens))
    want = family.logits_at(params, jnp.asarray(tokens), jnp.asarray(at), spec)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=LOGPROB_TOL, rtol=0)
    for mode in ("state-bf16", "router-bf16", "int8"):
        low = family.logits_at(params, jnp.asarray(tokens), jnp.asarray(at),
                               spec, mode)
        assert np.abs(np.asarray(low) - np.asarray(want)).max() > 20 * LOGPROB_TOL


def test_the_reference_builds_a_lengths_programs_once(model):
    """A pass over a length builds its four programs (both mixers, the
    experts, the head) side by side, once: a second pass of that length
    builds none but a head of more rows, and the positions asked for go
    through the head in whole blocks of rows, so 5 of them read what the
    first 5 of 70 read."""
    spec, family, _, params = model
    tokens = jnp.asarray(prompts(1, [ref.Q_BLOCK])[0], jnp.int32)
    ref._BUILT.clear()
    few = family.logits_at(params, tokens, jnp.arange(5), spec)
    assert sorted(k[0] for k in ref._BUILT) == [
        "_experts", "_head_block", "_mix", "_mix"]
    family.logits_at(params, tokens, jnp.arange(64), spec)
    assert len(ref._BUILT) == 4
    more = family.logits_at(params, tokens, jnp.arange(70), spec)
    assert len(ref._BUILT) == 5   # a head of 128 rows
    assert few.shape == (5, spec["vocab_size"]) and more.shape[0] == 70
    np.testing.assert_array_equal(np.asarray(few), np.asarray(more[:5]))
    family.logits_at(params, tokens[:64], jnp.arange(5), spec, "int8")
    assert len(ref._BUILT) == 9


def test_the_reference_passes_the_real_tokens_alone_through_the_experts(
        model, monkeypatch):
    """What follows the last position asked for is the caller's right
    padding: the experts take the blocks of `ROWS` tokens that hold a real
    one (a ragged last block too) and the answer at the positions asked for
    is the whole pass's."""
    spec, family, _, params = model
    tokens = jnp.asarray(prompts(1, [192])[0], jnp.int32)
    at = jnp.arange(60, 70)
    whole = family.logits_at(params, tokens, at, spec)
    monkeypatch.setattr(ref, "ROWS", 128)
    ref._BUILT.clear()
    two = family.logits_at(params, tokens, jnp.arange(120, 130), spec)
    assert sorted(k[3][0][0][0] for k in ref._BUILT if k[0] == "_experts") \
        == [64, 128]
    np.testing.assert_allclose(
        np.asarray(two), np.asarray(family.logits_at(
            params, tokens[:130], jnp.arange(120, 130), spec)), atol=2e-6)
    monkeypatch.setattr(ref, "ROWS", 64)   # two blocks of the three
    part = family.logits_at(params, tokens, at, spec)
    np.testing.assert_allclose(np.asarray(part), np.asarray(whole), atol=2e-6)
    # ... and the padding's rows did go without their term
    full = ref.hidden_states(params, tokens, spec)
    cut = ref.hidden_states(params, tokens, spec, real=70)
    np.testing.assert_allclose(np.asarray(cut[:70]), np.asarray(full[:70]),
                               atol=2e-6)
    assert np.abs(np.asarray(cut[128:]) - np.asarray(full[128:])).max() > 1e-3


@pytest.mark.parametrize("path,length", [
    ("bucket", 5),     # one bucket
    ("bucket", 16),    # a whole bucket
    ("chunked", 21),   # two chunks, the last one padded
    ("chunked", 48),   # three whole chunks
])
def test_prefill_and_decode_agree_with_the_plain_reference(model, path, length):
    """Both prefill paths, then 30 decoded tokens through pages (the 2 GQA
    layers of the 8), convolution tails and state matrices, against the
    reference's one cache-less pass, on log-probabilities; a bfloat16 state
    and a bfloat16 router both fail the tolerance."""
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    try:
        assert (length > eng.ecfg.prefill_chunk) == (path == "chunked")
        assert eng.k_pages.shape == (2, 1, 96, PAGE, 2 * 16)
        assert eng.state["conv"].shape == (6, 2, 3, 96)
        assert eng.state["gdn"].shape == (6, 2, 8, 4 * 8)
        assert eng.state["gdn"].dtype == jnp.float32
        assert eng.prefix is None  # off by derivation: state beside pages
        prompt = prompts(1, [length], seed=length)[0]
        got = eng.generate(prompt, max_tokens=30)
    finally:
        eng.stop()
    want = reference_logprobs(model, prompt, got["token_ids"])
    served = np.asarray(got["logprobs"])
    assert np.abs(served - want[np.arange(30), got["token_ids"]]).max() \
        < LOGPROB_TOL
    for mode in ("state-bf16", "router-bf16"):
        low = reference_logprobs(model, prompt, got["token_ids"], mode)
        assert np.abs(served - low[np.arange(30), got["token_ids"]]).max() \
            > 10 * LOGPROB_TOL


def test_the_engine_counts_where_the_choices_fell_and_the_shared_rows(model):
    _, _, cfg, params = model
    eng = engine_for(cfg, params)
    before = common.counters()
    try:
        eng.generate(prompts(1, [21])[0], max_tokens=6)
    finally:
        eng.stop()
    after = common.counters()
    made = common.counter_delta(before, after, "serve_moe_choices", kind="all")
    held = common.counter_delta(before, after, "serve_moe_choices", kind="held")
    assert made > 0 and 0 < held < made       # 4 of 16 held: about a quarter
    assert common.counter_delta(before, after, "serve_moe_shared_rows") > 0
    assert common.counter_delta(
        before, after, "serve_recurrent_state_slot_steps", state="live") > 0


# -- the benchmark's side ----------------------------------------------------


def test_the_configuration_is_the_catalogs_row_but_for_its_four_cuts():
    import json

    spec = common.load_json("configs", CONFIG + ".json")
    manifest = common.load_manifest()
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    cuts = ["num_hidden_layers", "gqa_layers", "n_routed_experts", "vocab_size"]
    assert entry["reduced"] == cuts == list(spec["reduced"])
    assert entry["source"] == spec["source"]
    assert 1 <= len(entry["why"]) <= 200  # the driver refuses a longer line
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == spec["source"])
    except OSError:
        pytest.skip("no catalog beside the guides here")
    differs = {k for k, v in row["config"].items() if spec.get(k, "-") != v}
    assert differs == set(cuts)
    assert {k: spec["published"][k] for k in cuts} == {
        k: row["config"][k] for k in cuts}
    assert spec["n_routed_experts_total"] == row["config"]["n_routed_experts"]
    for item in ("kda_low_rank", "kda_biases", "kda_decay", "kda_decay_init",
                 "gqa_gate", "gqa_norm", "router", "weights", "torch_dtype"):
        assert item in spec["assumed"]


def test_the_cell_is_an_entry_and_its_readers_list_it():
    """Entries are found by name: a later PR appends behind them."""
    manifest = common.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert entry["traffic"] == "serve-mixedlen"
    assert 1 <= len(entry["why"]) <= 200
    cell = common.load_cell(CELL)
    assert cell["engine"] == {"max_seq_len": 16384, "max_batch_size": 64,
                              "max_pages": 12289}
    assert [m["name"] for m in cell["end_to_end"]] == ["tpot_mean_ms", "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    new = by_name["gdn_chunk_device_share"]
    assert new["workloads"] == ["olmo-hybrid-7b.serve-reason", CELL]
    assert (new["layer"], new["moves"], new["source"]) == (
        "kernels", "tpot_mean_ms", "device_trace")
    for wanted in ("gdn_chunk_device_share", "gdn_chunk_roofline",
                   "gdn_step_roofline", "gdn_step_device_share",
                   "recurrent_state_live_share", "paged_decode_roofline",
                   "paged_chunk_attn_roofline", "prefill_device_ms_per_ktok",
                   "moe_experts_touched_share", "moe_experts_skipped_share",
                   "moe_ffn_device_share.tpot", "moe_rows_padding_factor",
                   "moe_held_choice_share", "shared_expert_device_share",
                   "decode_live_slots.traced", "setup_compile_s"):
        assert wanted in names and by_name[wanted]["workloads"][-1] == CELL
    assert {m["moves"] for m in cell["per_layer"]} == {"tpot_mean_ms", "setup_s"}
    # the traffic file is SmallThinker's cell's, as it stands
    assert [c["weight"] for c in cell["traffic"]["classes"]] == [0.8, 0.2]
    assert cell["traffic"]["schedule_seed"] == 41


def test_the_schedules_longest_sequence_fits_the_engine():
    from benchmark import traffic

    cell = common.load_cell(CELL)
    requests = traffic.requests(cell["traffic"], 1, cell["rate_rps"], 40.0,
                                cell["config"]["vocab_size"])
    longest = max(len(r["prompt_ids"]) + r["max_tokens"] for r in requests)
    assert longest <= cell["engine"]["max_seq_len"] == 16384
    assert max(max(r["prompt_ids"]) for r in requests) < 24576
    # ... and the check's reference passes have TWO lengths between them (a
    # length is three programs of the TPU compiler's time: the family's
    # `PAD_TO`), the short class all at the first
    family = common.family(cell["config"])
    replayed = {-(-(len(r["prompt_ids"]) + min(r["max_tokens"],
                                               cell["check"]["max_tokens"]))
                  // family.PAD_TO) * family.PAD_TO for r in requests}
    assert replayed == {8192, 16384}
    assert max(c["prompt_len"]["max"] for c in cell["traffic"]["classes"][:1]) \
        + cell["check"]["max_tokens"] <= 8192


def test_the_cpu_rehearsal_runs_the_new_cell(monkeypatch):
    """`solar-open2-250b.serve-mixedlen` end to end at the family's tiny
    cut: the benchmark's own drivers, generator, warm-up, window, replay and
    comparison with the plain reference, BOTH classes shrunk to the tiny
    engine (benchmark/tests/tiny.py shrinks a mix's one `prompt_len` and
    knows no `classes`: PERF.md section 7). The joined readers read the
    recorded counters."""
    import ray_tpu
    from benchmark import drive

    manifest = common.load_manifest()
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    spec = tiny_spec(CONFIG)
    # the family pads a replayed sequence to 8192 (few lengths, for prompts
    # of up to 14 k): the tiny cut's are under 96 tokens
    monkeypatch.setattr(common.family(spec), "PAD_TO", ref.Q_BLOCK)
    cell = common.load_cell(CELL)
    cell["config"] = spec
    cell["engine"] = dict(max_seq_len=96, max_batch_size=4, max_pages=97,
                          page_size=PAGE, prefill_buckets=(8, 16),
                          prefill_chunk=16, decode_span=4, busy_span=2,
                          cache_dtype="float32")
    short, long_ = cell["traffic"]["classes"]
    short["prompt_len"].update(median=8, min=3, max=24)
    short["output_len"].update(median=10, min=4, max=16)
    long_["prompt_len"].update(median=40, min=34, max=60)  # three chunks and up
    long_["output_len"].update(median=12, min=6, max=20)
    cell.update(rate_rps=12.0, drain_cap_s=60)
    # the tiny cut runs in the configuration's bfloat16, as the cell does
    cell["check"].update(sample=4, max_tokens=8, limits={
        "logprob_rms_err": 0.02, "logprob_p50_err": 0.02})
    assert entry["chips"] == 1
    args = argparse.Namespace(seed=2**31 + 52, seconds=1.0, trace=0, sweep="")
    ray_tpu.init(num_tpus=1)  # the CPU has no TPU resource to schedule on
    try:
        out = drive.measure(cell, args, {"platform": "cpu"},
                            common.CompileWatch(), time.perf_counter())
    finally:
        ray_tpu.shutdown()
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 10
    assert {"tpot_mean_ms", "setup_s"} <= set(out["end_to_end"])
    ctx = {"counters": out["counters"]}
    held = common.load_reader("moe_held_choice_share")(ctx)
    assert 10 < held < 40   # 4 of 16 held: a quarter of the choices
    assert 0 < common.load_reader("recurrent_state_live_share")(ctx) <= 100
    touched = common.load_reader("moe_experts_touched_share")(ctx)
    assert 0 < touched <= 100
    assert common.load_reader("moe_rows_padding_factor")(ctx) > 1
    empty = {"counters": ({}, {})}
    assert common.load_reader("moe_held_choice_share")(empty) is None
    # the new reader finds nothing in a trace without the kernel, and says so
    read = common.load_reader("gdn_chunk_device_share")
    assert read({"trace": {"busy_s": 1.0, "ops": {}, "modules": {},
                           "module_ops": {}}}) is None
    op = "%gdn_chunk.3 = (f32[1,64,256,128]{3,2,1,0}) custom-call(f32[] %a)"
    assert read({"trace": {"busy_s": 2.0, "ops": {op: [0.5, 6]}, "modules": {},
                           "module_ops": {}}}) == 25.0
