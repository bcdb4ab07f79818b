"""The plain reference: Mistral's and Mixtral's forward pass as published,
in straightforward jax.numpy, float32, matmuls at `highest` precision. No
kernels, no cache, no batching, nothing imported from the program.

    h = x + Attention(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attention: GQA, rotary embedding on half-split pairs (the HF layout),
               causal softmax(q k^T / sqrt(head_dim)) v
    FFN dense: w_out(silu(x w_gate) * (x w_in))
    FFN sparse (Mixtral): top-k of the router's logits, softmax over the
               selected k, sum_k weight_k * FFN_expert_k(x). Dropless:
               every token reaches its k experts.

It works one layer at a time (a Python loop over per-layer jitted calls)
so that only one layer's weights are ever held in float32, and attention
goes over blocks of queries, so a row of 8192 tokens fits beside the
model. The weights are the tree benchmark/weights.py makes from --seed.

`mode` is the control's part: the same forward in the precision below the
configuration's bfloat16. "int8" and "fp8" round every matmul weight;
"kv-int8" and "kv-fp8" leave the weights and round what a quantised cache
would hold, each token's keys (after the rotary embedding) and values,
scaled per token and head (tests/test_reference.py, tools/control.py)."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Q_BLOCK = 1024


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _round_through(w, amax, mode: str):
    if mode == "int8":
        scale = amax / 127.0
        return jnp.round(w / scale) * scale
    if mode == "fp8":
        scale = amax / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown control precision {mode!r}")


def quantize(w, mode: Optional[str]):
    """Round a weight to `mode` and back, scaled per output column (the
    last axis): what an int8 or fp8 weight path would feed the matmul."""
    if mode is None or mode.startswith("kv-") or w.ndim < 2:
        return w
    w = w.astype(jnp.float32)
    reduce_axes = tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=reduce_axes, keepdims=True) + 1e-30
    return _round_through(w, amax, mode)


def quantize_cache(x, mode: Optional[str]):
    """Round keys or values [T, kv_heads, hd] as a "kv-..." cache would
    store them: one scale per token and head."""
    if mode is None or not mode.startswith("kv-"):
        return x
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True) + 1e-30
    if mode == "kv-fp8":
        # not through a float8 type: inside this large program the TPU
        # compiler elides the convert pair as excess precision (chip, PR 24:
        # the control then read 2.6e-6). reduce_precision is never elided;
        # 4 exponent and 3 mantissa bits reach 240, so that is the scale
        scale = amax / 240.0
        return jax.lax.reduce_precision(x / scale, 4, 3) * scale
    return _round_through(x, amax, mode[3:])


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [T, heads, hd], positions 0..T-1; pairs (i, i + hd/2)."""
    T, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lp, spec, mode=None):
    T = x.shape[0]
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    q = rope(jnp.einsum("td,dhk->thk", x, lp["wq"]), spec["rope_theta"])
    k = rope(jnp.einsum("td,dhk->thk", x, lp["wk"]), spec["rope_theta"])
    v = jnp.einsum("td,dhk->thk", x, lp["wv"])
    k, v = quantize_cache(k, mode), quantize_cache(v, mode)
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    block = min(Q_BLOCK, T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qhk,thk->hqt", qb, k) / hd ** 0.5
        visible = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v)

    # checkpointed: a backward pass through this recomputes one block's
    # probabilities at a time and never stores all [H, T, T] of them
    starts = jnp.arange(0, T, block)
    o = jax.lax.map(jax.checkpoint(one_block), starts).reshape(T, H, hd)
    return jnp.einsum("thk,hkd->td", o, lp["wo"])


def dense_ffn(x, w_in, w_gate, w_out):
    return (jax.nn.silu(x @ w_gate) * (x @ w_in)) @ w_out


def sparse_ffn(x, lp, spec, mode=None):
    """One expert at a time (a scan over the stacked expert weights, which
    stay in their stored type until their turn), so that only one expert's
    float32 copy is ever live: all eight of a layer are 5 GiB."""
    k = spec["num_experts_per_tok"]
    logits = x @ lp["router"]                               # [T, E]
    top, ids = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top, axis=-1)                  # over the k

    def one_expert(out, expert):
        e, w_in, w_gate, w_out = expert
        gate = jnp.sum(jnp.where(ids == e, weights, 0.0), axis=-1)  # [T]
        y = dense_ffn(x, *(quantize(w, mode).astype(jnp.float32)
                           for w in (w_in, w_gate, w_out)))
        return out + gate[:, None] * y, None

    experts = (jnp.arange(spec["num_local_experts"]),
               lp["w_in"], lp["w_gate"], lp["w_out"])
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), experts)
    return out


def block(x, lp, spec, mode=None):
    eps = spec["rms_norm_eps"]
    h = x + attention(rms_norm(x, lp["ln1"], eps), lp, spec, mode)
    n = rms_norm(h, lp["ln2"], eps)
    if spec.get("num_local_experts"):
        return h + sparse_ffn(n, lp, spec, mode)
    return h + dense_ffn(n, lp["w_in"], lp["w_gate"], lp["w_out"])


def _prepared(lp, mode):
    """One layer's weights in float32, rounded through `mode` first; stacked
    expert weights (3 axes) wait for their turn in sparse_ffn."""
    def prepare(name, w):
        if w.ndim == 3 and name in ("w_in", "w_gate", "w_out"):
            return w
        if name.startswith("w") or name == "router":
            return quantize(w, mode).astype(jnp.float32)
        return w.astype(jnp.float32)

    return {name: prepare(name, w) for name, w in lp.items()}


@functools.partial(jax.jit, static_argnames=("spec_items", "mode"))
def _layer(x, lp, spec_items, mode):
    with jax.default_matmul_precision("highest"):
        return block(x, _prepared(lp, mode), dict(spec_items), mode)


@functools.partial(jax.jit, static_argnames=("spec_items", "mode"))
def _layer_vjp(x, lp, g, spec_items, mode):
    """Cotangent g of the layer's output -> (cotangent of its input,
    gradient of its first norm weight)."""
    with jax.default_matmul_precision("highest"):
        prepared = _prepared(lp, mode)
        _, vjp = jax.vjp(
            lambda x, ln1: block(x, {**prepared, "ln1": ln1},
                                 dict(spec_items), mode),
            x, prepared["ln1"])
        return vjp(g)


@functools.partial(jax.jit, static_argnames=("spec_items", "mode"))
def _head(x, final_norm, lm_head, spec_items, mode):
    spec = dict(spec_items)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), spec["rms_norm_eps"])
        return x @ quantize(lm_head, mode).astype(jnp.float32)


def spec_items(spec: Dict[str, Any]):
    """The numbers of the configuration file, hashable for jit."""
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float)) and not isinstance(v, bool)))


def hidden_states(params, tokens, spec, mode=None, keep_inputs=False):
    """tokens [T] -> final hidden state [T, D] (before the last norm);
    with keep_inputs also the list of every layer's input."""
    items = spec_items(spec)
    x = params["embed"][tokens].astype(jnp.float32)
    inputs = []
    for i in range(spec["num_hidden_layers"]):
        if keep_inputs:
            inputs.append(x)
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer(x, lp, items, mode)
    return (x, inputs) if keep_inputs else x


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`."""
    x = hidden_states(params, tokens, spec, mode)
    return _head(x[positions], params["final_norm"], params["lm_head"],
                 spec_items(spec), mode)


def nll_and_norm_grads(params, tokens, targets, spec, mode=None):
    """Per-position negative log-likelihood [T] of `targets`, and the
    gradient of its mean with respect to every layer's first norm weight
    [L, D]: a backward pass through every layer's attention and FFN, by
    one jax.vjp per layer, so no more than one layer is ever differentiated
    at a time."""
    items = spec_items(spec)
    x, inputs = hidden_states(params, tokens, spec, mode, keep_inputs=True)

    def tail(x):
        logits = _head(x, params["final_norm"], params["lm_head"], items, mode)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        nll = lse - picked
        return jnp.mean(nll), nll

    (_, nll), g_x = jax.value_and_grad(tail, has_aux=True)(x)
    grads = []
    for i in reversed(range(spec["num_hidden_layers"])):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        g_x, g_ln1 = _layer_vjp(inputs[i], lp, g_x, items, mode)
        grads.append(g_ln1)
    return nll, jnp.stack(grads[::-1])
