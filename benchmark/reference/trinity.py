"""The plain reference of the `afmoe` family (Trinity-Mini): the layer's
equations in straightforward jax.numpy, float32, matmuls at `highest`
precision. No kernels, no sorting, no cache, nothing imported from the
program.

    x = sqrt(hidden_size) * E[token]
    every layer, four RMSNorms:
        x = x + N_post_attn(Attn(N_in(x)))
        x = x + N_post_mlp(FFN(N_pre_mlp(x)))
    Attn, h the normed input: q = h W_q, k = h W_k, v = h W_v, g = h W_g;
        q and k RMS-normalised per head (one weight vector each); in a
        `sliding_attention` layer rotary positions on q and k (half-split
        pairs) and keys i - window < j <= i; in a `full_attention` layer NO
        positions and every key j <= i; scores / sqrt(head_dim);
        out = (softmax(q k^T) v * sigmoid(g)) W_o. No biases.
    FFN of the `num_dense_layers` leading layers: W_down(silu(h W_gate) * (h W_up))
    FFN of the others: s = sigmoid(h W_r) over ALL the router's outputs; the
        chosen are the top k of s + b; w = s[chosen] WITHOUT the bias,
        w / (sum(w) + 1e-20) * route_scale;
        out = Shared(h) + sum over the HELD chosen experts of w_k Expert_k(h):
        the terms of experts held elsewhere are another chip's. Every held
        expert runs over every row, masked by its weight (0 where not chosen).
    final RMSNorm, the untied head over the rows of the vocabulary held.
    loss: the mean over positions of the cross-entropy.

It works one layer at a time (a Python loop over per-layer jitted calls),
attention over blocks of queries and the experts one at a time, so a row of
8192 fits. The weights are the program's tree (`layers`: a list of segments,
each a tuple with one dict per layer of its period, stacked over repeats),
made by benchmark/families/trinity_afmoe.py from --seed; the leaves' names are the
program's (`ln1` = N_in, `ln1_post` = N_post_attn, `ln2` = N_pre_mlp,
`ln2_post` = N_post_mlp, `wg` the head gate, `sh_*` the shared expert).

One departure of the PROGRAM from these equations: it renormalises the
chosen scores over their sum + 1e-6 (parallel/moe.py, every sigmoid-routed
model's) where this file holds the published 1e-20: a relative 1e-7 of a
weight, under float32's own rounding of the sum.

`mode`: the other reading of each assumed equation, which a test holds the
program apart from (`rope-in-full`, `no-head-gate`, `bias-in-weight`,
`pre-norm-only`), and the precisions: `bf16` (every product's operands
rounded to bfloat16: what the stated precision itself costs) and the
controls BELOW it, `int8` and `fp8` (every matrix rounded per output column,
benchmark/reference/model.py `quantize`), against which the limits of the
cell's check are set."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.model import quantize, rms_norm, rope, spec_items

Q_BLOCK = 1024
EQUATION_MODES = ("rope-in-full", "no-head-gate", "bias-in-weight",
                  "pre-norm-only")
PRECISION_MODES = ("bf16", "int8", "fp8")
# the last expert layer's leaves that only the grouped product's backward
# reaches: the held experts' three matrices and the router's
EXPERT_LEAVES = ("w_in", "w_gate", "w_out")


def static(spec: Dict[str, Any]):
    """The numbers of the configuration and its layer types, hashable."""
    return spec_items(spec) + (("layer_types", tuple(spec["layer_types"])),)


def kind_of(l: int, spec) -> str:
    return "swa" if spec["layer_types"][l] == "sliding_attention" else "attn"


def _mm(expr: str, a, b, mode):
    """One product; under `bf16` both operands rounded to bfloat16."""
    if mode == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jnp.einsum(expr, a, b, preferred_element_type=jnp.float32)


def head_norm(x, w, eps):
    """x [T, heads, hd] normalised over each head's own lanes."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def gqa(h, lp, spec, kind: str, mode=None):
    T = h.shape[0]
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    eps = spec["rms_norm_eps"]
    q = head_norm(_mm("td,dhk->thk", h, lp["wq"], mode), lp["q_norm"], eps)
    k = head_norm(_mm("td,dhk->thk", h, lp["wk"], mode), lp["k_norm"], eps)
    v = _mm("td,dhk->thk", h, lp["wv"], mode)
    if kind == "swa" or mode == "rope-in-full":
        q, k = rope(q, spec["rope_theta"]), rope(k, spec["rope_theta"])
    k = jnp.repeat(k, H // KVH, axis=1)
    v = jnp.repeat(v, H // KVH, axis=1)
    block = min(Q_BLOCK, T)
    window = spec["sliding_window"] if kind == "swa" else T

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = _mm("qhk,thk->hqt", qb, k, mode) / hd ** 0.5
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(T)[None, :]
        visible = (j <= i) & (j > i - window)
        p = jax.nn.softmax(jnp.where(visible[None], s, -jnp.inf), axis=-1)
        return _mm("hqt,thk->qhk", p, v, mode)

    o = jax.lax.map(jax.checkpoint(one_block),
                    jnp.arange(0, T, block)).reshape(T, H, hd)
    if mode != "no-head-gate":
        o = o * jax.nn.sigmoid(_mm("td,dhk->thk", h, lp["wg"], mode))
    return _mm("thk,hkd->td", o, lp["wo"], mode)


def gated_ffn(h, w_in, w_gate, w_out, mode=None):
    return _mm("tf,fd->td", jax.nn.silu(_mm("td,df->tf", h, w_gate, mode))
               * _mm("td,df->tf", h, w_in, mode), w_out, mode)


def route(h, lp, spec, mode=None):
    """-> c [T, router outputs] float32: a token's weights at its chosen
    experts, zero elsewhere."""
    k = spec["num_experts_per_tok"]
    with jax.default_matmul_precision("highest"):  # the scores, always
        s = jax.nn.sigmoid(h @ lp["router"])
    b = lp["router_bias"]
    _, ids = jax.lax.top_k(s + b, k)
    w = jnp.take_along_axis(s + b if mode == "bias-in-weight" else s, ids, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * spec["route_scale"]
    return jnp.sum(jax.nn.one_hot(ids, s.shape[-1]) * w[..., None], axis=1)


def moe(h, lp, spec, mode=None, shared: bool = True, first=None, held=None):
    """The share of the expert layer this chip holds: the held experts
    `first` .. `first + held` (None: the configuration's) and, `shared`, the
    whole shared expert."""
    first = spec["experts_first"] if first is None else first
    held = lp["w_in"].shape[0] if held is None else held
    c = route(h, lp, spec, mode)

    def one_expert(out, expert):
        gate, w_in, w_gate, w_out = expert
        y = gated_ffn(h, *(w.astype(jnp.float32)
                           for w in (w_in, w_gate, w_out)), mode)
        return out + gate[:, None] * y, None

    gates = c[:, first:first + held].T
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          (gates, lp["w_in"], lp["w_gate"], lp["w_out"]))
    if shared:
        out = out + gated_ffn(h, lp["sh_in"], lp["sh_gate"], lp["sh_out"],
                              mode)
    return out


def _rounds(mode):
    return mode if mode in ("int8", "fp8") else None


def layer(x, lp, kind: str, spec, mode=None):
    """One layer; its second half is dense where it holds no router."""
    eps = spec["rms_norm_eps"]
    post = mode != "pre-norm-only"
    a = gqa(rms_norm(x, lp["ln1"], eps), lp, spec, kind, mode)
    x = x + (rms_norm(a, lp["ln1_post"], eps) if post else a)
    h = rms_norm(x, lp["ln2"], eps)
    if "router" in lp:
        m = moe(h, lp, spec, mode)
    else:
        m = gated_ffn(h, lp["w_in"], lp["w_gate"], lp["w_out"], mode)
    return x + (rms_norm(m, lp["ln2_post"], eps) if post else m)


def _prepared(lp, mode):
    """One layer's weights in float32, the matrices rounded through a
    control precision first (an expert's by itself); unrounded, the stacked
    experts stay as stored until their turn."""
    def prepare(name, w):
        if name in ("w_in", "w_gate", "w_out") and w.ndim == 3:
            if _rounds(mode) is None:
                return w
            return jax.vmap(lambda m: quantize(m, _rounds(mode)))(w)
        if w.ndim >= 2 and name != "router":
            return quantize(w, _rounds(mode)).astype(jnp.float32)
        return w.astype(jnp.float32)

    return {name: prepare(name, w) for name, w in lp.items()}


@functools.partial(jax.jit, static_argnames=("kind", "items", "mode"))
def _layer(x, lp, kind, items, mode):
    with jax.default_matmul_precision("highest"):
        return layer(x, _prepared(lp, mode), kind, dict(items), mode)




@functools.partial(jax.jit, static_argnames=("kind", "items", "mode", "deep"))
def _layer_vjp(x, lp, g, kind, items, mode, deep):
    """Cotangent g of the layer's output -> (cotangent of its input, the
    gradient of its first norm weight and, `deep`, of the held experts'
    three matrices and the router's)."""
    with jax.default_matmul_precision("highest"):
        prepared = _prepared(lp, mode)
        probe = {"ln1": prepared["ln1"]}
        if deep:
            probe.update(router=prepared["router"], **{
                name: prepared[name].astype(jnp.float32)
                for name in EXPERT_LEAVES})
        _, vjp = jax.vjp(
            lambda x, probe: layer(x, {**prepared, **probe}, kind,
                                   dict(items), mode), x, probe)
        return vjp(g)


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _head(x, final_norm, lm_head, items, mode):
    spec = dict(items)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), spec["rms_norm_eps"])
        return _mm("td,dv->tv", x,
                   quantize(lm_head, _rounds(mode)).astype(jnp.float32), mode)


def layers_of(params):
    """The tree's layers in the model's order, one dict each."""
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                yield jax.tree.map(lambda a: a[rep], stacked)


def hidden_states(params, tokens, spec, mode=None, keep_inputs=False):
    """tokens [T] -> final hidden state [T, D] (before the last norm);
    with keep_inputs also every layer's input."""
    items = static(spec)
    x = params["embed"][tokens].astype(jnp.float32) * spec["hidden_size"] ** 0.5
    inputs = []
    for l, lp in enumerate(layers_of(params)):
        inputs.append(x)
        x = _layer(x, lp, kind_of(l, spec), items, mode)
    assert len(inputs) == spec["num_hidden_layers"]
    return (x, inputs) if keep_inputs else x


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`."""
    x = hidden_states(params, tokens, spec, mode)
    return _head(x[positions], params["final_norm"], params["lm_head"],
                 static(spec), mode)


def nll_and_grads(params, tokens, targets, spec, mode=None):
    """Per-position negative log-likelihood [T] of `targets`, and the
    gradient of its mean: `ln1` [L, D], every layer's first norm weight,
    and of the LAST expert layer the held experts' `w_in`, `w_gate`,
    `w_out` and the `router`: a backward pass through every layer, one
    jax.vjp a layer."""
    items = static(spec)
    x, inputs = hidden_states(params, tokens, spec, mode, keep_inputs=True)

    def tail(x):
        logits = _head(x, params["final_norm"], params["lm_head"], items, mode)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        nll = lse - picked
        return jnp.mean(nll), nll

    (_, nll), g_x = jax.value_and_grad(tail, has_aux=True)(x)
    layers = list(layers_of(params))
    last_moe = max(l for l, lp in enumerate(layers) if "router" in lp)
    grads, ln1 = {}, []
    for l in reversed(range(len(layers))):
        g_x, g = _layer_vjp(inputs[l], layers[l], g_x, kind_of(l, spec),
                            items, mode, l == last_moe)
        ln1.append(g.pop("ln1"))
        grads.update(g)
    return nll, {"ln1": jnp.stack(ln1[::-1]), **grads}
