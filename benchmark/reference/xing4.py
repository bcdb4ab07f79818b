"""The plain reference of the `xing4_0` family (Xing4.0-29B-A4B): the
DeepSeek-V3 block inside manifold-constrained hyper-connections
(arXiv:2512.24880), with a multi-token prediction block in the loss, in
straightforward jax.numpy, float32, matmuls at `highest` precision. No
kernels, no sorting, no cache, nothing imported from the program.

    streams: x[j] = E[token] for j = 1..n (n = `hc_mult`); after the last
        layer h = sum_j x[j], then the final RMSNorm and the untied head.
    the residual path, round EVERY sublayer F (its own phi, b, a):
        v = vec(x) (the token's n streams side by side, n D wide)
        u = (v / sqrt(mean(v^2) + hc_eps)) phi            [n n + 2 n]
        H_pre  = sigmoid(a_pre u[0:n] + b[0:n])
        H_post = 2 sigmoid(a_post u[n:2n] + b[n:2n])
        M = exp(clip(a_res mat(u[2n:]) + mat(b[2n:]), clamp_min, clamp_max))
        `hc_sinkhorn_iters` times: M /= column sums + hc_eps; M /= row sums
        + hc_eps; H_res = M (the gradient flows through every round)
        y = F(N(sum_j H_pre[j] x[j]));  x+[i] = sum_j H_res[i,j] x[j] + H_post[i] y
    attention (h the normed input): c_q = N_q(h W_qa), q = c_q W_qb (heads of
        qk_nope | qk_rope); c = N_kv(h W_kva); k_r = turn(h W_kr), ONE for all
        heads; k = [c W_kb ; k_r], v = c W_vb; causal softmax(q k^T s) v, W_o.
        turn: interleaved pairs (2i, 2i+1) by pos * inv_i; yarn:
        inv = extra / factor * (1 - m) + extra * m, extra_i = theta^(-2i/R),
        m_i = 1 - clip((i - lo) / (hi - lo), 0, 1), lo = floor(cd(beta_fast)),
        hi = ceil(cd(beta_slow)), cd(r) = R ln(orig / (2 pi r)) / (2 ln theta);
        s = (qk_nope + qk_rope)^-0.5 (0.1 mscale_all_dim ln(factor) + 1)^2;
        cos and sin times mscale-factor ratio (1 here).
    second half: the leading `first_k_dense_replace` layers a SwiGLU of
        `intermediate_size`; the others s = sigmoid(h W_r) over ALL the
        router's outputs, the chosen the top k of s + b, weights s[chosen]
        WITHOUT the bias over their sum + 1e-20, times
        `routed_scaling_factor`; out = Shared(h) + the HELD chosen experts'
        weighted sum (experts held elsewhere are another chip's).
    MTP (DeepSeek-V3 2.2, depth 1): g_i = W_p [N_h(h_i) ; N_e(E[t_{i+1}])],
        g copied into n streams, ONE more expert layer with parameters of
        its own, summed, a final norm of its own, the SHARED head;
        L_mtp = mean over the T - 1 positions that have one of CE(., t_{i+2});
        L = mean CE(main head, t_{i+1}) + `mtp_loss_weight` L_mtp.
        A row's `targets` ARE t_{i+1}, so the block embeds `targets`, and
        its own targets are `targets` moved left by one, the last masked.

It works one layer at a time (a Python loop over per-layer jitted calls),
attention over blocks of queries and the experts one at a time, so a row of
8192 fits. The weights are the program's tree (`layers`: a list of segments,
each a tuple with one dict per layer of its period, stacked over repeats;
`mtp`: the block's `layer`, unstacked, beside `h_norm`, `e_norm`, `proj`
[2D, D] and `final_norm`), made by benchmark/families/xing4_mhc.py from
--seed; the leaves' names are the program's (`hc1_*` round the attention,
`hc2_*` round the second half; `*_phi` [n, D, n n + 2 n], `*_a` = (a_pre,
a_post, a_res)).

Departures of the PROGRAM from these equations, none in the mathematics: it
computes (v phi) / rms(v) for (v / rms(v)) phi; it renormalises the chosen
scores over their sum + 1e-6 where this file holds 1e-20 (a relative 1e-7).

`mode`: the other reading of each assumed equation, which a test holds the
program apart from (`one-stream`: a plain residual, no hyper-connection;
`static-mhc`: a_* = 0, the mixing the same for every token; `sinkhorn-1`:
one round; `no-yarn`: theta alone and no score factor; `no-mtp`: the loss is
the main head's alone; `bias-in-weight`), and the precisions: `bf16` (every
product's operands rounded to bfloat16) and the controls BELOW it, `int8` and
`fp8` (every matrix rounded per output column)."""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.model import quantize, rms_norm

Q_BLOCK = 1024
EQUATION_MODES = ("one-stream", "static-mhc", "sinkhorn-1", "no-yarn",
                  "no-mtp", "bias-in-weight")
PRECISION_MODES = ("bf16", "int8", "fp8")
EXPERT_LEAVES = ("w_in", "w_gate", "w_out")
HC_LEAVES = tuple(f"hc{i}_{part}" for i in (1, 2) for part in ("phi", "b", "a"))


def static(spec: Dict[str, Any]):
    """What the equations read of the configuration, hashable for jit."""
    scaling = spec.get("rope_scaling") or {}
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, bool)))) + (
        ("rope_scaling", tuple(sorted(scaling.items()))),)


def _mm(expr: str, a, b, mode):
    """One product; under `bf16` both operands rounded to bfloat16."""
    if mode == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
    return jnp.einsum(expr, a, b, preferred_element_type=jnp.float32)


# -- rotary lanes under yarn ---------------------------------------------------


def yarn(spec, mode=None):
    """-> (inverse frequencies [R / 2], the scores' scale, the tables' own
    factor)."""
    R, N = spec["qk_rope_head_dim"], spec["qk_nope_head_dim"]
    theta = float(spec["rope_theta"])
    extra = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    s = dict(spec.get("rope_scaling") or ())
    if mode == "no-yarn" or not s:
        return extra, (N + R) ** -0.5, 1.0

    def cd(turns):
        return (R * math.log(s["original_max_position_embeddings"]
                             / (turns * 2 * math.pi)) / (2 * math.log(theta)))

    lo = max(math.floor(cd(s["beta_fast"])), 0)
    hi = min(math.ceil(cd(s["beta_slow"])), R - 1)
    ramp = (jnp.arange(R // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3)
    m = 1.0 - jnp.clip(ramp, 0.0, 1.0)

    def mscale(by):
        return 0.1 * by * math.log(s["factor"]) + 1.0 if s["factor"] > 1 else 1.0

    return (extra / s["factor"] * (1.0 - m) + extra * m,
            (N + R) ** -0.5 * mscale(s["mscale_all_dim"]) ** 2,
            mscale(s["mscale"]) / mscale(s["mscale_all_dim"]))


def turn(x, inv, grow=1.0):
    """x [T, heads, R] at positions 0..T-1; interleaved pairs (2i, 2i+1)."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * grow, jnp.sin(ang)[:, None, :] * grow
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


# -- the sublayers -------------------------------------------------------------


def mla(h, lp, spec, mode=None):
    """h [T, D] (normed) -> [T, D]."""
    T = h.shape[0]
    N, eps = spec["qk_nope_head_dim"], spec["rms_norm_eps"]
    inv, scale, grow = yarn(spec, mode)
    c_q = rms_norm(_mm("td,dr->tr", h, lp["wq_a"], mode), lp["q_ln"], eps)
    q = _mm("tr,rhk->thk", c_q, lp["wq_b"], mode)
    c = rms_norm(_mm("td,dr->tr", h, lp["wkv_a"], mode), lp["kv_ln"], eps)
    k_r = turn(_mm("td,dr->tr", h, lp["wkr"], mode)[:, None], inv, grow)[:, 0]
    q_n, q_r = q[..., :N], turn(q[..., N:], inv, grow)
    k_n = _mm("tl,lhn->thn", c, lp["wk_b"], mode)
    v = _mm("tl,lhv->thv", c, lp["wv_b"], mode)
    block = min(Q_BLOCK, T)

    def one_block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, block, 0)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, block, 0)
        s = (_mm("qhn,thn->hqt", qn, k_n, mode)
             + _mm("qhr,tr->hqt", qr, k_r, mode)) * scale
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return _mm("hqt,thv->qhv", p, v, mode)

    o = jax.lax.map(jax.checkpoint(one_block), jnp.arange(0, T, block))
    return _mm("thv,hvd->td", o.reshape(T, *v.shape[1:]), lp["wo"], mode)


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
    if spec.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * spec["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(ids, s.shape[-1]) * w[..., None], axis=1)


def moe(h, lp, spec, mode=None, shared: bool = True, first=None, held=None):
    """The share of the expert layer this chip holds: the held experts
    `first` .. `first + held` (None: the configuration's) and, `shared`, the
    whole shared expert."""
    first = spec["held_experts_first"] if first is None else first
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


# -- the residual path -----------------------------------------------------------


def mixing(x, lp, tag: str, spec, mode=None):
    """x [n, T, D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    n, T, D = x.shape
    v = jnp.moveaxis(x, 0, 1).reshape(T, n * D)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + spec["hc_eps"])
    u = _mm("tk,kc->tc", v, lp[tag + "_phi"].reshape(n * D, -1), mode)
    a, b = lp[tag + "_a"], lp[tag + "_b"]
    if mode == "static-mhc":
        a = jnp.zeros_like(a)
    pre = jax.nn.sigmoid(a[0] * u[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * u[:, n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * u[:, 2 * n:] + b[2 * n:],
                         spec["mhc_h_res_clamp_min"],
                         spec["mhc_h_res_clamp_max"])).reshape(T, n, n)
    eps = spec["hc_eps"]
    for _ in range(1 if mode == "sinkhorn-1" else spec["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # column sums
        m = m / (jnp.sum(m, axis=2, keepdims=True) + eps)   # row sums
    return pre, post, m


def residual(x, lp, tag: str, spec, mode, sublayer):
    """x [n, T, D] round one sublayer (h [T, D] -> y [T, D])."""
    if mode == "one-stream":
        return x + sublayer(x[0])[None]
    pre, post, res = mixing(x, lp, tag, spec, mode)
    y = sublayer(jnp.einsum("tj,jtd->td", pre, x))
    return jnp.einsum("tij,jtd->itd", res, x) + post.T[:, :, None] * y[None]


def layer(x, lp, spec, mode=None):
    """One layer over the streams x [n, T, D]; its second half is dense
    where it holds no router."""
    eps = spec["rms_norm_eps"]
    x = residual(x, lp, "hc1", spec, mode, lambda h: mla(
        rms_norm(h, lp["ln1"], eps), lp, spec, mode))

    def half(h):
        h = rms_norm(h, lp["ln2"], eps)
        if "router" in lp:
            return moe(h, lp, spec, mode)
        return gated_ffn(h, lp["w_in"], lp["w_gate"], lp["w_out"], mode)

    return residual(x, lp, "hc2", spec, mode, half)


def expand(e, spec, mode=None):
    """[T, D] -> the streams [n, T, D]."""
    n = 1 if mode == "one-stream" else spec["hc_mult"]
    return jnp.broadcast_to(e[None], (n, *e.shape))


# -- jitted pieces -----------------------------------------------------------------


def _rounds(mode):
    return mode if mode in ("int8", "fp8") else None


def _prepared(lp, mode):
    """One layer's weights in float32, the matrices rounded through a
    control precision first (an expert's by itself); unrounded, the stacked
    experts stay as stored until their turn."""
    def prepare(name, w):
        if name in EXPERT_LEAVES and w.ndim == 3:
            if _rounds(mode) is None:
                return w
            return jax.vmap(lambda m: quantize(m, _rounds(mode)))(w)
        if w.ndim >= 2 and name != "router":
            return quantize(w, _rounds(mode)).astype(jnp.float32)
        return w.astype(jnp.float32)

    return {name: prepare(name, w) for name, w in lp.items()}


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _layer(x, lp, items, mode):
    with jax.default_matmul_precision("highest"):
        return layer(x, _prepared(lp, mode), dict(items), mode)


def _probe(prepared, deep: bool):
    """The leaves of one layer whose gradient is compared."""
    probe = {name: prepared[name] for name in ("ln1",) + HC_LEAVES}
    if deep:
        probe.update(router=prepared["router"],
                     router_bias=prepared["router_bias"], **{
            name: prepared[name].astype(jnp.float32) for name in EXPERT_LEAVES})
    return probe


@functools.partial(jax.jit, static_argnames=("items", "mode", "deep"))
def _layer_vjp(x, lp, g, items, mode, deep):
    """Cotangent g of the layer's output -> (cotangent of its input, the
    gradient of its first norm weight and its residual paths' leaves and,
    `deep`, of the held experts' three matrices, the router's and its
    bias's)."""
    with jax.default_matmul_precision("highest"):
        prepared = _prepared(lp, mode)
        _, vjp = jax.vjp(
            lambda x, probe: layer(x, {**prepared, **probe}, dict(items),
                                   mode), x, _probe(prepared, deep))
        return vjp(g)


def _head(h, final_norm, lm_head, spec, mode):
    h = rms_norm(h, final_norm.astype(jnp.float32), spec["rms_norm_eps"])
    return _mm("td,dv->tv", h,
               quantize(lm_head, _rounds(mode)).astype(jnp.float32), mode)


def _nll(logits, targets):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]


def mtp_logits(h, targets, params, mtp_layer, probe, spec, mode):
    """The prediction block over the collapsed stream h [T, D]; `mtp_layer`
    prepared, `probe` its compared leaves (`proj` among them)."""
    mp, eps = params["mtp"], spec["rms_norm_eps"]
    e = params["embed"][targets].astype(jnp.float32)
    g = jnp.concatenate([rms_norm(h, mp["h_norm"].astype(jnp.float32), eps),
                         rms_norm(e, mp["e_norm"].astype(jnp.float32), eps)], -1)
    g = _mm("tk,kd->td", g, probe["proj"], mode)
    lp = {**mtp_layer, **{k: v for k, v in probe.items() if k != "proj"}}
    x = layer(expand(g, spec, mode), lp, spec, mode)
    return _head(jnp.sum(x, 0), mp["final_norm"], params["lm_head"], spec, mode)


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _tail(h, targets, params, items, mode):
    """The collapsed stream h [T, D] -> (the main head's nll [T], the
    prediction block's nll [T] (its last position has no target: 0), and
    the gradient of L = mean(nll) + weight x mean over T - 1 of the block's,
    by h and by the block's compared leaves)."""
    spec = dict(items)
    with jax.default_matmul_precision("highest"):
        mtp_layer = _prepared(params["mtp"]["layer"], mode)
        probe = {**_probe(mtp_layer, False), "proj": quantize(
            params["mtp"]["proj"], _rounds(mode)).astype(jnp.float32)}
        has = jnp.arange(targets.shape[0]) < targets.shape[0] - 1

        def loss(h, probe):
            nll = _nll(_head(h, params["final_norm"], params["lm_head"],
                             spec, mode), targets)
            if mode == "no-mtp":
                return jnp.mean(nll), (nll, jnp.zeros_like(nll))
            after = _nll(mtp_logits(h, targets, params, mtp_layer, probe,
                                    spec, mode), jnp.roll(targets, -1)) * has
            total = jnp.mean(nll) + spec["mtp_loss_weight"] * (
                jnp.sum(after) / jnp.maximum(jnp.sum(has), 1))
            return total, (nll, after)

        (_, (nll, after)), (g_h, g_probe) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(h, probe)
        return nll, after, g_h, g_probe


def layers_of(params):
    """The tree's layers in the model's order, one dict each."""
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                yield jax.tree.map(lambda a: a[rep], stacked)


def hidden_states(params, tokens, spec, mode=None, keep_inputs=False):
    """tokens [T] -> the collapsed stream [T, D] (before the last norm);
    with keep_inputs also every layer's input streams."""
    items = static(spec)
    x = expand(params["embed"][tokens].astype(jnp.float32), spec, mode)
    inputs = []
    for lp in layers_of(params):
        inputs.append(x)
        x = _layer(x, lp, items, mode)
    assert len(inputs) == spec["num_hidden_layers"]
    h = jnp.sum(x, axis=0)
    return (h, inputs) if keep_inputs else h


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _head_at(h, final_norm, lm_head, items, mode):
    with jax.default_matmul_precision("highest"):
        return _head(h, final_norm, lm_head, dict(items), mode)


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`
    (the main head's)."""
    h = hidden_states(params, tokens, spec, mode)
    return _head_at(h[positions], params["final_norm"], params["lm_head"],
                    static(spec), mode)


def losses_and_grads(params, tokens, targets, spec, mode=None):
    """-> (the main head's nll [T], the prediction block's nll [T], the
    gradient of L): `ln1` [L + 1, D], every layer's first norm weight, the
    prediction block's last; every residual path's leaves (`HC_LEAVES`,
    stacked the same way); of the LAST expert layer of the trunk the held
    experts' `w_in`, `w_gate`, `w_out`, the `router` and its `router_bias`
    (zero: the bias is in the choice only); the block's `proj`.
    A backward pass through every layer, one jax.vjp a layer."""
    items = static(spec)
    h, inputs = hidden_states(params, tokens, spec, mode, keep_inputs=True)
    nll, after, g_h, g_tail = _tail(h, targets, params, items, mode)
    g_x = jnp.broadcast_to(g_h[None], inputs[0].shape)
    layers = list(layers_of(params))
    last_moe = max(l for l, lp in enumerate(layers) if "router" in lp)
    grads = {"proj": g_tail.pop("proj")}
    stacked = [g_tail]
    for l in reversed(range(len(layers))):
        g_x, g = _layer_vjp(inputs[l], layers[l], g_x, items, mode,
                            l == last_moe)
        stacked.append({name: g.pop(name) for name in ("ln1",) + HC_LEAVES})
        grads.update(g)
    for name in ("ln1",) + HC_LEAVES:
        grads[name] = jnp.stack([g[name] for g in stacked[::-1]])
    return nll, after, grads


def nll_and_grads(params, tokens, targets, spec, mode=None):
    nll, _, grads = losses_and_grads(params, tokens, targets, spec, mode)
    return nll, grads
