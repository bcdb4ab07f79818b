"""The plain reference of the LFM2 mixture-of-experts family (LFM2-8B-A1B,
LiquidAI): every layer's equations in straightforward jax.numpy, float32,
matmuls at `highest` precision. No kernels, no cache, no pages, no state
carried between calls, no capacity, nothing imported from the program.

    every layer l:  h = h + Mix_l(RMSNorm(h));  h = h + Second_l(RMSNorm(h))
    RMSNorm: x / sqrt(mean(x^2) + eps) * w, a learned weight, eps `norm_eps`.
    Mix_l by `layer_types[l]`:
      conv            [B ; C ; x] = u W_in   (D -> 3 D, in this order)
                      z_t = sum_{j < K} w[j] * (B * x)_{t-(K-1)+j}
                      (depthwise, causal, zero before t = 0, K = conv_L_cache
                      taps a channel, no bias, no activation)
                      mix = (C * z) W_out
      full_attention  q = u W_q (H heads of hd), k = u W_k, v = u W_v (KVH)
                      q, k RMS-normalised per head over its hd lanes (weights
                      [hd] shared by the heads), then turned by the rotary
                      embedding (rotate-half pairs, `rope_theta`) at the
                      token's position; mix = softmax(q k^T / sqrt(hd),
                      causal, GQA) v W_o
    Second_l: l < num_dense_layers: W_2 (silu(f W_1) * f W_3), width
              `intermediate_size`; after them the experts, width
              `moe_intermediate_size`:
              s = sigmoid(f W_g) (float32);  S = top-k indices of s + b
              (b a per-expert bias, `use_expert_bias`; it takes part in the
              choice only);  g_e = s_e / (sum_{e in S} s_e + 1e-6) (if
              `norm_topk_prob`) * `routed_scaling_factor`, e in S
              out = sum_{e in S} g_e * W_2e (silu(f W_1e) * f W_3e)
              Dropless: every token reaches its k experts.
    Head: final RMSNorm, logits = h E^T with the tied embedding, no scale.

Departures from the published modeling code, none in the mathematics: the
projections are stored as the program's tree stores them (`c_in` [D, 3D],
`c_conv` [K, D] with tap K-1 on the current position, `wq` [D, H, hd], the
experts stacked [E, D, F]); every expert runs over every token and is
weighted by its gate, zero where the token did not choose it (one expert's
float32 copy live at a time: a layer's 32 are 1.4 GB); attention goes over
blocks of queries and the head over blocks of the vocabulary, so the
reference fits beside 9.3 GB of bfloat16 weights. The weights are the
program's tree (`layers`: a list of segments, each a tuple with one dict
per layer of its period, stacked over repeats); the reference walks it in
order and tells a layer's kind and second half by its index.

`mode` is the control's part: "int8" / "fp8" round every matmul weight (the
router and the tied table in the head too) per output column."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.model import dense_ffn, quantize, rms_norm, rope

Q_BLOCK = 256
VOCAB_BLOCK = 32768
MATMULS = frozenset(("c_in", "c_out", "wq", "wk", "wv", "wo", "router",
                     "w_in", "w_gate", "w_out"))


def static(spec: Dict[str, Any]):
    """What the equations read of the configuration, hashable for jit."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in spec.items()
        if isinstance(v, (int, float, bool, list))))


def kind_of(l: int, spec: Dict[str, Any]) -> str:
    return "conv" if spec["layer_types"][l] == "conv" else "attn"


def short_conv(u, lp):
    """u [T, D] -> [T, D]."""
    T, D = u.shape
    bcx = u @ lp["c_in"]
    B, C, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    K = lp["c_conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, D), u.dtype), B * x], axis=0)
    z = jnp.zeros_like(u)
    for j in range(K):  # tap K-1 multiplies the current position
        z = z + padded[j:j + T] * lp["c_conv"][j]
    return (C * z) @ lp["c_out"]


def attention(u, lp, spec):
    T = u.shape[0]
    H, KVH = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["hidden_size"] // H
    eps, theta = spec["norm_eps"], float(spec["rope_theta"])
    q = jnp.einsum("td,dhk->thk", u, lp["wq"])
    k = jnp.einsum("td,dhk->thk", u, lp["wk"])
    v = jnp.einsum("td,dhk->thk", u, lp["wv"])
    q = rope(rms_norm(q, lp["q_norm"], eps), theta)
    k = rope(rms_norm(k, lp["k_norm"], eps), theta)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    block = min(Q_BLOCK, T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qhk,thk->hqt", qb, k) / hd ** 0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v)

    o = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, H, hd)
    return jnp.einsum("thk,hkd->td", o, lp["wo"])


def route(f, lp, spec):
    """f [T, D] -> (gates [T, k], expert ids [T, k])."""
    k = spec["num_experts_per_tok"]
    s = jax.nn.sigmoid(f @ lp["router"])                    # [T, E]
    chosen_by = s + lp["router_bias"] if spec["use_expert_bias"] else s
    _, ids = jax.lax.top_k(chosen_by, k)
    g = jnp.take_along_axis(s, ids, axis=-1)
    if spec["norm_topk_prob"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    return g * spec["routed_scaling_factor"], ids


def sparse_ffn(f, lp, spec, mode=None):
    g, ids = route(f, lp, spec)

    def one_expert(out, expert):
        e, w_in, w_gate, w_out = expert
        gate = jnp.sum(jnp.where(ids == e, g, 0.0), axis=-1)  # [T]
        y = dense_ffn(f, *(quantize(w, mode).astype(jnp.float32)
                           for w in (w_in, w_gate, w_out)))
        return out + gate[:, None] * y, None

    experts = (jnp.arange(spec["num_experts"]),
               lp["w_in"], lp["w_gate"], lp["w_out"])
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(f), experts)
    return out


def _prepared(lp, mode):
    """One layer's weights in float32, the matmuls' rounded through `mode`
    first; stacked expert weights (3 axes) wait for their turn."""
    def prepare(name, w):
        if w.ndim == 3 and name in ("w_in", "w_gate", "w_out"):
            return w
        if name in MATMULS:
            w = quantize(w, mode)
        return w.astype(jnp.float32)

    return {name: prepare(name, w) for name, w in lp.items()}


@functools.partial(jax.jit, static_argnames=("kind", "dense", "items", "mode"))
def _layer(h, lp, kind, dense, items, mode):
    spec = dict(items)
    eps = spec["norm_eps"]
    with jax.default_matmul_precision("highest"):
        lp = _prepared(lp, mode)
        u = rms_norm(h, lp["ln1"], eps)
        if kind == "conv":
            h = h + short_conv(u, lp)
        else:
            h = h + attention(u, lp, spec)
        f = rms_norm(h, lp["ln2"], eps)
        if dense:
            return h + dense_ffn(f, lp["w_in"], lp["w_gate"], lp["w_out"])
        return h + sparse_ffn(f, lp, spec, mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_block(x, final_norm, table, eps, mode):
    """x [n, D], table [rows, D] (a block of the tied embedding)."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        # the head's matrix is the table transposed: its output columns
        # are the table's rows
        return x @ quantize(table.T, mode).astype(jnp.float32)


def hidden_states(params, tokens, spec, mode=None):
    """tokens [T] -> final hidden state [T, D] (before the last norm)."""
    items = static(spec)
    h = params["embed"][tokens].astype(jnp.float32)
    l = 0
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                lp = jax.tree.map(lambda a: a[rep], stacked)
                h = _layer(h, lp, kind_of(l, spec),
                           l < spec["num_dense_layers"], items, mode)
                l += 1
    assert l == spec["num_hidden_layers"]
    return h


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`."""
    x = hidden_states(params, tokens, spec, mode)[positions]
    table = params["embed"]
    blocks = [_head_block(x, params["final_norm"], table[i:i + VOCAB_BLOCK],
                          spec["norm_eps"], mode)
              for i in range(0, table.shape[0], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)
