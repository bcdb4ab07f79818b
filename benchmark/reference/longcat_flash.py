"""The plain reference of the LongCat-Flash family (the language model of
meituan-longcat/LongCat-Flash-Omni): every layer's equations in
straightforward jax.numpy, float32, matmuls at `highest` precision. No
kernels, no cache, no pages, no absorbed form, nothing imported from the
program.

    one published layer, stream h (a DOUBLE block with a shortcut):
      for i in (0, 1):
          h = h + MLA_i(RMSNorm_in_i(h))
          b = RMSNorm_post_i(h)
          if i == 0: s = MoE(b)         # computed here ...
          h = h + SwiGLU_i(b)           # dense, `ffn_hidden_size`, SiLU
      h = h + s                         # ... joined here
    so the second attention never sees s.
    RMSNorm: x / sqrt(mean(x^2) + eps) * w, eps `rms_norm_eps`.
    MLA(x): cq = RMSNorm(x W_qa)                         [q_lora_rank]
            q  = (cq W_qb) * sqrt(hidden / q_lora_rank)  (`mla_scale_q_lora`)
                 as H heads of [q_n qk_nope | q_r qk_rope]
            (c_raw | k_r) = x W_kva                      [kv_lora_rank | qk_rope]
            c  = RMSNorm(c_raw) * sqrt(hidden / kv_lora_rank)
                                                         (`mla_scale_kv_lora`)
            q_r (per head) and the ONE k_r the heads share are turned by
            the rotary embedding at the token's position: interleaved pairs
            (2i, 2i+1) by angle pos * rope_theta^(-2i / qk_rope), no scaling
            (k_n | v)_head = c W_kvb                     [H x (qk_nope | v_head)]
            scores (q_n . k_n + q_r . k_r) / sqrt(qk_nope + qk_rope), causal,
            softmax; out = concat_heads(p v) W_o
    MoE(b): score = softmax(b W_r) over ALL `n_routed_experts_total` +
            `zero_expert_num` outputs (float32); the `moe_topk` are chosen by
            score + bias (a per-expert buffer: in the choice only); weights
            w_e = routed_scaling_factor * score_e of the chosen, NOT divided
            by their sum;
            out = sum_{chosen e < total, HELD} w_e SwiGLU_e(b)
                  + (sum_{chosen e >= total} w_e) * b     (identity experts)
            A chosen expert that is not held (`held_experts_first` .. +
            the count the weights hold) adds nothing here: its term is
            another chip's. With every expert held that is the whole layer.
    Head: final RMSNorm, logits = h W_head (untied).

Departures from the published modeling code, none in the mathematics: the
projections are stored as the program's tree stores them (the two blocks'
leaves named for the block, `wq_a0` / `wq_a1` [D, ql]; W_kva as `wkv_a` [D,
kl] and `wkr` [D, rope]; W_kvb as `wk_b` [kl, H, nope] and `wv_b` [kl, H,
v]; `wo` [H, v, D], `f_in` [D, F]; the experts stacked [E, D, Fe]); every held expert runs over every token and is weighted by its gate,
zero where the token did not choose it (one expert's float32 copy live at a
time); attention goes over blocks of queries and the head over blocks of
the vocabulary, one block's weights in float32 at a time, so 8.4 k
positions fit beside 10.3 GB of bfloat16 weights.

`mode` is the control's part: "int8" / "fp8" round every matmul weight (the
router and the head too) per output column; "router-bf16" leaves the weights
alone and computes the router's logits and its softmax in bfloat16, which
the configuration does not state (its scores are float32)."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.model import dense_ffn, quantize, rms_norm

Q_BLOCK = 256
VOCAB_BLOCK = 8192
ATTN = ("a_ln", "wq_a", "q_ln", "wq_b", "wkv_a", "wkr", "kv_ln", "wk_b",
        "wv_b", "wo")
MATMULS = frozenset(("wq_a", "wq_b", "wkv_a", "wkr", "wk_b", "wv_b", "wo",
                     "f_in", "f_gate", "f_out", "router", "w_in", "w_gate",
                     "w_out"))


def static(spec: Dict[str, Any]):
    """What the equations read of the configuration, hashable for jit."""
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, bool))))


def turn(x, theta):
    """x [T, heads, R] at positions 0..T-1; interleaved pairs (2i, 2i+1)."""
    T, _, R = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def mla(x, lp, spec):
    """x [T, D] (normed) -> [T, D]; lp: one block's attention weights."""
    T, D = x.shape
    ql, kl = spec["q_lora_rank"], spec["kv_lora_rank"]
    N, R = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    eps, theta = spec["rms_norm_eps"], float(spec["rope_theta"])
    cq = rms_norm(x @ lp["wq_a"], lp["q_ln"], eps)
    q = jnp.einsum("tr,rhk->thk", cq, lp["wq_b"])
    if spec["mla_scale_q_lora"]:
        q = q * (D / ql) ** 0.5
    c = rms_norm(x @ lp["wkv_a"], lp["kv_ln"], eps)
    if spec["mla_scale_kv_lora"]:
        c = c * (D / kl) ** 0.5
    k_r = turn((x @ lp["wkr"])[:, None], theta)[:, 0]       # [T, R], shared
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


def rounds_weights(mode):
    """The mode as `quantize` takes it: None unless it rounds weights."""
    return mode if mode in ("int8", "fp8") else None


def route(b, lp, spec, mode=None):
    """b [T, D] -> (weights [T, k], expert ids [T, k]) over every output
    of the router, held or not."""
    if mode == "router-bf16":
        low = jnp.bfloat16
        score = jax.nn.softmax(b.astype(low) @ lp["router"].astype(low),
                               axis=-1).astype(jnp.float32)
    else:
        score = jax.nn.softmax(b @ lp["router"], axis=-1)
    _, ids = jax.lax.top_k(score + lp["router_bias"], spec["moe_topk"])
    w = jnp.take_along_axis(score, ids, axis=-1)
    return w * spec["routed_scaling_factor"], ids


def moe(b, lp, spec, mode=None, identity=True):
    """The expert layer's part that the held experts (the weights' leading
    axis, from `held_experts_first`) and, if `identity`, the zero-compute
    experts give."""
    w, ids = route(b, lp, spec, mode)
    mode = rounds_weights(mode)
    first, total = spec["held_experts_first"], spec["n_routed_experts_total"]

    def one_expert(out, expert):
        e, w_in, w_gate, w_out = expert
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)   # [T]
        y = dense_ffn(b, *(quantize(m, mode).astype(jnp.float32)
                           for m in (w_in, w_gate, w_out)))
        return out + gate[:, None] * y, None

    held = lp["w_in"].shape[0]
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(b), (
        first + jnp.arange(held), lp["w_in"], lp["w_gate"], lp["w_out"]))
    if identity and spec["zero_expert_num"]:
        out = out + jnp.sum(jnp.where(ids >= total, w, 0.0), -1)[:, None] * b
    return out


def _f32(lp, names, mode, i=None):
    """The named weights in float32 (block i's: `<name><i>`), the matmuls'
    rounded through `mode` first."""
    mode = rounds_weights(mode)
    out = {}
    for name in names:
        w = lp[name] if i is None else lp[f"{name}{i}"]
        out[name] = (quantize(w, mode) if name in MATMULS else w).astype(
            jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("i", "items", "mode"))
def _attend(h, lp, i, items, mode):
    """-> (h + MLA_i(norm(h)), its post-attention norm)."""
    spec = dict(items)
    with jax.default_matmul_precision("highest"):
        w = _f32(lp, ATTN + ("p_ln",), mode, i)
        h = h + mla(rms_norm(h, w["a_ln"], spec["rms_norm_eps"]), w, spec)
        return h, rms_norm(h, w["p_ln"], spec["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("i", "mode"))
def _dense(b, lp, i, mode):
    with jax.default_matmul_precision("highest"):
        w = _f32(lp, ("f_in", "f_gate", "f_out"), mode, i)
        return dense_ffn(b, w["f_in"], w["f_gate"], w["f_out"])


@functools.partial(jax.jit, static_argnames=("items", "mode", "identity"))
def _experts(b, lp, items, mode, identity=True):
    with jax.default_matmul_precision("highest"):
        w = dict(lp, **_f32(lp, ("router", "router_bias"), mode))
        return moe(b, w, dict(items), mode, identity)


def layer(h, lp, spec, mode=None):
    """One published layer over h [T, D]."""
    items, s = static(spec), None
    for i in (0, 1):
        h, b = _attend(h, lp, i, items, mode)
        if i == 0:
            s = _experts(b, lp, items, mode)
        h = h + _dense(b, lp, i, mode)
    return h + s


def hidden_states(params, tokens, spec, mode=None):
    """tokens [T] -> final hidden state [T, D] (before the last norm)."""
    h = params["embed"][tokens].astype(jnp.float32)
    n = 0
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                h = layer(h, jax.tree.map(lambda a: a[rep], stacked), spec,
                          mode)
                n += 1
    assert n == spec["num_layers"]
    return h


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_block(x, final_norm, head, eps, mode):
    """x [n, D], head [D, columns] (a block of the head's columns)."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ quantize(head, rounds_weights(mode)).astype(jnp.float32)


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`."""
    x = hidden_states(params, tokens, spec, mode)[positions]
    head = params["lm_head"]
    blocks = [_head_block(x, params["final_norm"], head[:, i:i + VOCAB_BLOCK],
                          spec["rms_norm_eps"], mode)
              for i in range(0, head.shape[1], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)
