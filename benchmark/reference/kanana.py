"""The plain reference of the Kanana-2 mixture-of-experts family
(kakaocorp/kanana-2-30b-a3b-instruct-2601, `model_type` deepseek_v3: the
DeepSeek-V3 block): every layer's equations in straightforward jax.numpy,
float32, matmuls at `highest` precision. No kernels, no cache, no pages, no
absorbed form, nothing imported from the program.

    every layer l, stream x:
      x = x + MLA_l(N1(x));  x = x + F_l(N2(x))
    N: RMSNorm, x / sqrt(mean(x^2) + eps) * w, eps `rms_norm_eps`.
    MLA(h): q = h W_q as H heads of [q_n qk_nope | q_r qk_rope]: ONE
            projection (`q_lora_rank` null: no bottleneck, no norm)
            (c_raw | k_raw) = h W_kva          [kv_lora_rank | qk_rope]
            c = N_kv(c_raw)
            q_r (per head) and the ONE k_r = k_raw the heads share are turned
            by the rotary embedding at the token's position: interleaved
            pairs (2i, 2i+1) by angle pos * rope_theta^(-2i / qk_rope), no
            scaling (`rope_scaling` null: no mscale)
            (k_n | v)_head = c W_kvb           [H x (qk_nope | v_head)]
            scores (q_n . k_n + q_r . k_r) / sqrt(qk_nope + qk_rope), causal,
            softmax; out = concat_heads(p v) W_o
    F_l, l < `first_k_dense_replace`: W_d (silu(W_g b) * (W_u b)), width
            `intermediate_size`.
    F_l after them: s = sigmoid(b W_r) (float32) over `n_routed_experts`
            outputs; the `num_experts_per_tok` are chosen by s + e (e a
            per-expert correction bias, a buffer: in the choice only; with
            `n_group` 1 and `topk_group` 1 the grouped choice is this plain
            top k); weights w = `routed_scaling_factor` * s[chosen] /
            (sum s[chosen] + 1e-20) (`norm_topk_prob`);
            y = sum_chosen w_e SwiGLU_e(b) + Shared(b): an expert is a SwiGLU
            of width `moe_intermediate_size`, Shared ONE SwiGLU of width
            `n_shared_experts` x `moe_intermediate_size`, every token,
            weight 1.
    Head: final RMSNorm, logits = x W_head (untied).

Departures from the published modeling code, none in the mathematics: the
projections are stored as the program's tree stores them (`wq` [D, H, nope +
rope]; W_kva as `wkv_a` [D, kl] and `wkr` [D, rope]; W_kvb as `wk_b` [kl, H,
nope] and `wv_b` [kl, H, v]; `wo` [H, v, D]; the experts stacked [E, D, F];
the shared experts' `sh_in`, `sh_gate` [D, n x F] and `sh_out` [n x F, D]);
the published rotary code first gathers a vector's even lanes and then its
odd ones and rotates halves, which is this rotation of pairs with the lanes
of q_r and k_r permuted alike, so every score is the same; every expert
runs over every token and is weighted by its gate, zero where the token did
not choose it (one expert's float32 copy live at a time); attention goes
over blocks of queries and the head over blocks of the vocabulary, so 22 k
positions fit beside 10.1 GB of bfloat16 weights. The weights are the
program's tree (`layers`: a list of segments, each a tuple with one dict per
layer of its period, stacked over repeats); the reference walks it in order
and tells a layer's second half by its index.

`mode` is the control's part: "int8" / "fp8" round every matmul weight (the
router and the head too) per output column; "router-bf16" leaves the weights
alone and computes the router's logits and scores in bfloat16, which the
configuration does not state (its scores are float32)."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.longcat_flash import (
    _head_block, rounds_weights, static, turn)
from benchmark.reference.model import dense_ffn, quantize, rms_norm

Q_BLOCK = 128
VOCAB_BLOCK = 16032          # 8 blocks of the 128256 columns
ATTN = ("ln1", "wq", "wkv_a", "wkr", "kv_ln", "wk_b", "wv_b", "wo", "ln2")
MATMULS = frozenset(("wq", "wkv_a", "wkr", "wk_b", "wv_b", "wo", "router",
                     "w_in", "w_gate", "w_out", "sh_in", "sh_gate", "sh_out"))


def mla(h, lp, spec):
    """h [T, D] (normed) -> [T, D]; lp: the layer's attention weights."""
    T = h.shape[0]
    N, R = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    eps, theta = spec["rms_norm_eps"], float(spec["rope_theta"])
    q = jnp.einsum("td,dhk->thk", h, lp["wq"])
    c = rms_norm(h @ lp["wkv_a"], lp["kv_ln"], eps)
    k_r = turn((h @ lp["wkr"])[:, None], theta)[:, 0]       # [T, R], shared
    q_n, q_r = q[..., :N], turn(q[..., N:], theta)
    k_n = jnp.einsum("tl,lhn->thn", c, lp["wk_b"])
    v = jnp.einsum("tl,lhv->thv", c, lp["wv_b"])
    block = min(Q_BLOCK, T)

    def one_block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, block, 0)
        s = (jnp.einsum("qhn,thn->hqt", qn, k_n)
             + jnp.einsum("qhr,tr->hqt", qr, k_r)) / (N + R) ** 0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thv->qhv", p, v)

    o = jax.lax.map(one_block, jnp.arange(0, T, block))
    return jnp.einsum("thv,hvd->td", o.reshape(T, *v.shape[1:]), lp["wo"])


def route(b, lp, spec, mode=None):
    """b [T, D] -> (weights [T, k], expert ids [T, k])."""
    if mode == "router-bf16":
        low = jnp.bfloat16
        score = jax.nn.sigmoid(
            b.astype(low) @ lp["router"].astype(low)).astype(jnp.float32)
    else:
        score = jax.nn.sigmoid(b @ lp["router"])
    _, ids = jax.lax.top_k(score + lp["router_bias"],
                           spec["num_experts_per_tok"])
    w = jnp.take_along_axis(score, ids, axis=-1)
    if spec["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * spec["routed_scaling_factor"], ids


def moe(b, lp, spec, mode=None):
    """The routed experts' weighted sum and the shared experts' product."""
    w, ids = route(b, lp, spec, mode)
    mode = rounds_weights(mode)

    def f32(m):
        return quantize(m, mode).astype(jnp.float32)

    def one_expert(out, expert):
        e, w_in, w_gate, w_out = expert
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)   # [T]
        return out + gate[:, None] * dense_ffn(
            b, f32(w_in), f32(w_gate), f32(w_out)), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(b), (
        jnp.arange(lp["w_in"].shape[0]), lp["w_in"], lp["w_gate"],
        lp["w_out"]))
    return out + dense_ffn(b, f32(lp["sh_in"]), f32(lp["sh_gate"]),
                           f32(lp["sh_out"]))


def _f32(lp, names, mode):
    """The named weights in float32, the matmuls' rounded through `mode`."""
    mode = rounds_weights(mode)
    return {name: (quantize(lp[name], mode) if name in MATMULS
                   else lp[name]).astype(jnp.float32) for name in names}


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _attend(x, lp, items, mode):
    """-> (x + MLA(N1(x)), N2 of that)."""
    spec = dict(items)
    with jax.default_matmul_precision("highest"):
        w = _f32(lp, ATTN, mode)
        x = x + mla(rms_norm(x, w["ln1"], spec["rms_norm_eps"]), w, spec)
        return x, rms_norm(x, w["ln2"], spec["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("mode",))
def _dense(b, lp, mode):
    with jax.default_matmul_precision("highest"):
        w = _f32(lp, ("w_in", "w_gate", "w_out"), mode)
        return dense_ffn(b, w["w_in"], w["w_gate"], w["w_out"])


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _experts(b, lp, items, mode):
    with jax.default_matmul_precision("highest"):
        w = dict(lp, **_f32(lp, ("router", "router_bias"), mode))
        return moe(b, w, dict(items), mode)


def layer(x, lp, spec, mode=None, dense=False):
    """One published layer over x [T, D]."""
    items = static(spec)
    x, b = _attend(x, lp, items, mode)
    return x + (_dense(b, lp, mode) if dense else _experts(b, lp, items, mode))


def hidden_states(params, tokens, spec, mode=None):
    """tokens [T] -> final hidden state [T, D] (before the last norm)."""
    x = params["embed"][tokens].astype(jnp.float32)
    n = 0
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                x = layer(x, jax.tree.map(lambda a: a[rep], stacked), spec,
                          mode, dense=n < spec["first_k_dense_replace"])
                n += 1
    assert n == spec["num_hidden_layers"]
    return x


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`."""
    x = hidden_states(params, tokens, spec, mode)[positions]
    head = params["lm_head"]
    blocks = [_head_block(x, params["final_norm"], head[:, i:i + VOCAB_BLOCK],
                          spec["rms_norm_eps"], mode)
              for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)
