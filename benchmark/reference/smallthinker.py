"""The plain reference of the window-and-full mixture-of-experts family
(SmallThinker-21BA3B-Instruct, PowerInfer): every layer's equations in
straightforward jax.numpy, float32, matmuls at `highest` precision. No
kernels, no cache, no pages, no ring, no state carried between calls,
nothing imported from the program.

    every layer l (w = sliding_window_layout[l], r = rope_layout[l]):
      s  = x W_r                       the router's logits [E], float32, from
                                       the layer's INPUT stream x, before any
                                       norm and before the attention
      h  = RMSNorm(x; ln1)
      q, k, v = h W_q, h W_k, h W_v    H / KVH / KVH heads of hd, no bias
      if r: q, k turned by the rotary embedding (half-split pairs
            (i, i + hd/2), `rope_theta`) at the token's position; r == 0:
            nothing encodes a position
      a  = softmax(q k^T / sqrt(hd) + mask) v, GQA;  mask: j <= i, and where
            w: j > i - sliding_window_size
      x  = x + a W_o
      b  = RMSNorm(x; ln2)
      S  = top-k indices of s (k = moe_num_active_primary_experts)
      p  = softmax over s[S] (`norm_topk_prob`: the chosen ones alone)
      x  = x + sum_{e in S} p_e * W_down,e (relu(b W_gate,e) * (b W_up,e))
    RMSNorm: x / sqrt(mean(x^2) + eps) * w, eps `rms_norm_eps`.
    Head: final RMSNorm, logits = x W_head (untied).

The weights are the program's tree (`layers`: a list of segments, each a
tuple with one dict per layer of its period, stacked over repeats; `w_in` is
W_up, `w_out` W_down, the experts stacked [E, D, F]); the reference walks it
in order and tells a layer's kind by its index in the two layouts. Every
expert runs over every token and is weighted by its gate, zero where the
token did not choose it (one expert's float32 copy live at a time);
attention goes over blocks of queries and the head over blocks of the
vocabulary, so 14 k positions fit beside 11.1 GB of bfloat16 weights.

`mode` is the control's part. "int8" / "fp8" round every matmul weight (the
router and the head too) per output column; "router-bf16" leaves the weights
alone and computes the router's logits and its softmax in bfloat16.
"router-after-norm" is the OTHER reading of "router placed before
attention": s = RMSNorm(x; ln1) W_r; "silu" gates the experts by SiLU, as
the repo's other expert families do. Neither is this configuration: the
program has to DISAGREE with both (tests/test_smallthinker_model.py)."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.model import quantize, rms_norm, rope

Q_BLOCK = 256
VOCAB_BLOCK = 32768
MATMULS = frozenset(("wq", "wk", "wv", "wo", "router", "w_in", "w_gate",
                     "w_out"))
WEIGHT_MODES = ("int8", "fp8")


def static(spec: Dict[str, Any]):
    """What the equations read of the configuration, hashable for jit."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in spec.items()
        if isinstance(v, (int, float, bool, list))))


def attention(h, lp, spec, rotary: bool, window: bool):
    """h [T, D] -> [T, D]."""
    T = h.shape[0]
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    W = spec["sliding_window_size"]
    q = jnp.einsum("td,dhk->thk", h, lp["wq"])
    k = jnp.einsum("td,dhk->thk", h, lp["wk"])
    v = jnp.einsum("td,dhk->thk", h, lp["wv"])
    if rotary:
        q, k = rope(q, float(spec["rope_theta"])), rope(k, float(spec["rope_theta"]))
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    block = min(Q_BLOCK, T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qhk,thk->hqt", qb, k) / hd ** 0.5
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(T)[None, :]
        seen = (j <= i) & (j > i - W) if window else j <= i
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v)

    o = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, H, hd)
    return jnp.einsum("thk,hkd->td", o, lp["wo"])


def route(r, lp, spec, mode=None):
    """r [T, D], what the router reads -> (weights [T, k], ids [T, k])."""
    if mode == "router-bf16":
        low = jnp.bfloat16
        s = (r.astype(low) @ lp["router"].astype(low)).astype(jnp.float32)
    else:
        s = r @ lp["router"]
    top, ids = jax.lax.top_k(s, spec["moe_num_active_primary_experts"])
    if mode == "router-bf16":
        return jax.nn.softmax(top.astype(jnp.bfloat16),
                              axis=-1).astype(jnp.float32), ids
    return jax.nn.softmax(top, axis=-1), ids


def experts(b, lp, p, ids, spec, mode=None):
    """b [T, D], the gates p [T, k] of the experts ids [T, k] -> [T, D]."""
    act = jax.nn.silu if mode == "silu" else jax.nn.relu
    rounded = mode if mode in WEIGHT_MODES else None

    def one_expert(out, expert):
        e, *weights = expert
        w_up, w_gate, w_down = (quantize(w, rounded).astype(jnp.float32)
                                for w in weights)
        gate = jnp.sum(jnp.where(ids == e, p, 0.0), axis=-1)  # [T]
        y = (act(b @ w_gate) * (b @ w_up)) @ w_down
        return out + gate[:, None] * y, None

    stacked = (jnp.arange(spec["moe_num_primary_experts"]),
               lp["w_in"], lp["w_gate"], lp["w_out"])
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(b), stacked)
    return out


def _prepared(lp, mode):
    """One layer's weights in float32, the matmuls' rounded through `mode`
    first; stacked expert weights (3 axes) wait for their turn."""
    rounded = mode if mode in WEIGHT_MODES else None

    def prepare(name, w):
        if w.ndim == 3 and name in ("w_in", "w_gate", "w_out"):
            return w
        if name in MATMULS:
            w = quantize(w, rounded)
        return w.astype(jnp.float32)

    return {name: prepare(name, w) for name, w in lp.items()}


@functools.partial(jax.jit,
                   static_argnames=("rotary", "window", "items", "mode"))
def _layer(x, lp, rotary, window, items, mode):
    spec = dict(items)
    eps = spec["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        lp = _prepared(lp, mode)
        h = rms_norm(x, lp["ln1"], eps)
        p, ids = route(h if mode == "router-after-norm" else x, lp, spec, mode)
        x = x + attention(h, lp, spec, rotary, window)
        b = rms_norm(x, lp["ln2"], eps)
        return x + experts(b, lp, p, ids, spec, mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_block(x, final_norm, head, eps, mode):
    """x [n, D], head [D, columns] (a block of the untied head)."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        rounded = mode if mode in WEIGHT_MODES else None
        return x @ quantize(head, rounded).astype(jnp.float32)


def hidden_states(params, tokens, spec, mode=None):
    """tokens [T] -> final hidden state [T, D] (before the last norm)."""
    items = static(spec)
    x = params["embed"][tokens].astype(jnp.float32)
    l = 0
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                lp = jax.tree.map(lambda a: a[rep], stacked)
                x = _layer(x, lp, bool(spec["rope_layout"][l]),
                           bool(spec["sliding_window_layout"][l]), items, mode)
                l += 1
    assert l == spec["num_hidden_layers"]
    return x


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`."""
    x = hidden_states(params, tokens, spec, mode)[positions]
    head = params["lm_head"]
    blocks = [_head_block(x, params["final_norm"], head[:, i:i + VOCAB_BLOCK],
                          spec["rms_norm_eps"], mode)
              for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)
