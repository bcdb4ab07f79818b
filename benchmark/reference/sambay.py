"""The plain reference of the SambaY family (Phi-4-mini-flash-reasoning,
arXiv:2507.06607): every layer's equations in straightforward jax.numpy,
float32, matmuls at `highest` precision, the recurrence a plain `lax.scan`
over time. No kernels, no cache, no pages, no state carried between calls,
nothing imported from the program.

    every layer l:  x = x + Mix_l(LN(x));  x = x + MLP(LN(x))
    LN: LayerNorm with weight and bias.  MLP: w_out(silu(x w_gate) * (x w_in))
    Mix_l, with N layers: l even, l <= N/2: Mamba;  l odd, l < N/2: window
    attention;  l = N/2 + 1: full attention (its K and V are the shared
    cache);  l even above: gated memory unit;  l odd above: cross attention.

    Mamba-1:  [u, z] = x W_in;  u = silu(conv1d_causal(u) + b)
              [dt, B, C] = u W_x;  dt = softplus(dt W_dt + b_dt);  A = -exp(A_log)
              S_t = exp(dt_t A) * S_{t-1} + (dt_t u_t) B_t^T;  y_t = C_t^T S_t + D u_t
              out = (y * silu(z)) W_out;  the middle Mamba layer hands on M = y
    GMU:      (M * silu(x W_in)) W_out
    Differential attention: heads in pairs (2j, 2j+1) -> (q1_j, q2_j),
              (k1_g, k2_g), V_g = [v1_g ; v2_g]; pair j reads KV pair j // 2:
              a_j = RMSNorm(softmax(q1 k1^T / 8) V - lam softmax(q2 k2^T / 8) V)
                    * w * (1 - lam0)
              lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
              lam0 = 0.8 - 0.6 exp(-0.3 l);  causal; in window layers a query
              at i sees keys i - window + 1 .. i.
    Cross attention: queries and output projection only, over the full
              layer's K and V.  No positional encoding anywhere.
    Head: final LayerNorm, logits = x E^T with the tied embedding.

One layer's weights are cast to float32 at a time and the head goes over
blocks of the vocabulary, so the reference fits beside 7.7 GB of bfloat16
weights. The weights are the program's tree (`layers`: a list of segments,
each a tuple with one dict per layer of its period, stacked over repeats);
the reference walks it in order and tells a layer's kind by its index.

`mode` is the control's part: "int8" / "fp8" round every matmul weight (and
the tied table in the head) per output column; "state-bf16" rounds the scan
state to bfloat16 after every step, what a bfloat16 state would hold."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.model import quantize, spec_items

Q_BLOCK = 256
VOCAB_BLOCK = 32768
MATMULS = frozenset((
    "w_in", "w_gate", "w_out", "m_in", "m_x", "m_dt", "m_out", "g_in",
    "g_out", "wq", "wk", "wv", "wo"))


def kind_of(l: int, n_layers: int) -> str:
    half = n_layers // 2
    if l <= half:
        return "mamba" if l % 2 == 0 else "window"
    if l == half + 1:
        return "full"
    return "gmu" if l % 2 == 0 else "cross"


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def mlp(x, lp):
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_in"])) @ lp["w_out"]


def mamba(x, lp, mode=None):
    """x [T, D] -> (out [T, D], M [T, Di])."""
    T = x.shape[0]
    Di = lp["m_D"].shape[0]
    K = lp["m_conv"].shape[0]
    N = lp["m_A_log"].shape[0]
    R = lp["m_dt"].shape[0]
    uz = x @ lp["m_in"]
    u, z = uz[:, :Di], uz[:, Di:]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), u.dtype), u], axis=0)
    conv = lp["m_conv_b"]
    for j in range(K):  # tap K-1 multiplies the current position
        conv = conv + padded[j:j + T] * lp["m_conv"][j]
    u = jax.nn.silu(conv)
    xdbc = u @ lp["m_x"]
    dt = jax.nn.softplus(xdbc[:, :R] @ lp["m_dt"] + lp["m_dt_b"])
    Bm, Cm = xdbc[:, R:R + N], xdbc[:, R + N:]
    A = -jnp.exp(lp["m_A_log"])                      # [N, Di]

    def step(S, xs):
        u_t, dt_t, b_t, c_t = xs
        S = jnp.exp(dt_t[None, :] * A) * S + (dt_t * u_t)[None, :] * b_t[:, None]
        if mode == "state-bf16":
            # not through the type: XLA elides a convert pair as excess
            # precision; reduce_precision (8 exponent, 7 mantissa bits) stays
            S = jax.lax.reduce_precision(S, 8, 7)
        return S, c_t @ S + lp["m_D"] * u_t

    _, y = jax.lax.scan(step, jnp.zeros((N, Di), jnp.float32), (u, dt, Bm, Cm))
    return (y * jax.nn.silu(z)) @ lp["m_out"], y


def gmu(x, lp, M):
    return (M * jax.nn.silu(x @ lp["g_in"])) @ lp["g_out"]


def keys_values(x, lp):
    k = jnp.einsum("td,dhk->thk", x, lp["wk"]) + lp["bk"]
    v = jnp.einsum("td,dhk->thk", x, lp["wv"]) + lp["bv"]
    return k, v


def diff_attention(x, lp, k, v, l, eps, window=None):
    """x [T, D]; k, v [T, KVH, hd] (this layer's or the full layer's)."""
    T = x.shape[0]
    q = jnp.einsum("td,dhk->thk", x, lp["wq"]) + lp["bq"]
    hd = q.shape[-1]
    q1, q2 = q[:, 0::2], q[:, 1::2]                   # [T, H/2, hd]
    group = q1.shape[1] // (k.shape[1] // 2)
    k1 = jnp.repeat(k[:, 0::2], group, axis=1)
    k2 = jnp.repeat(k[:, 1::2], group, axis=1)
    V = jnp.repeat(jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1), group, axis=1)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * l)
    lam = (jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"]))
           - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lam0)
    block = min(Q_BLOCK, T)

    def one_block(start):
        rows = start + jnp.arange(block)
        seen = rows[:, None] >= jnp.arange(T)[None, :]
        if window is not None:
            seen &= jnp.arange(T)[None, :] > rows[:, None] - window

        def softmax_v(qh, kh):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, 0)
            s = jnp.einsum("qhk,thk->hqt", qb, kh) / hd ** 0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqt,thk->qhk", p, V)

        return softmax_v(q1, k1) - lam * softmax_v(q2, k2)

    a = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, -1, 2 * hd)
    a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)
    a = a * lp["sub_w"] * (1.0 - lam0)
    H = q.shape[1]
    return jnp.einsum("thk,hkd->td", a.reshape(T, H, hd), lp["wo"]) + lp["bo"]


WEIGHT_MODES = ("int8", "fp8")


def _rounded(w, mode):
    """A matmul weight through the control's precision (not a state mode)."""
    return quantize(w, mode if mode in WEIGHT_MODES else None)


def _prepared(lp, mode):
    return {name: (_rounded(w, mode) if name in MATMULS else w).astype(jnp.float32)
            for name, w in lp.items()}


@functools.partial(jax.jit, static_argnames=("kind", "items", "mode"))
def _layer(x, lp, shared, l, kind, items, mode):
    """-> (x, shared): `shared` holds what later layers read, the middle
    Mamba layer's M and the full layer's K and V."""
    spec = dict(items)
    eps = spec["layer_norm_eps"]
    with jax.default_matmul_precision("highest"):
        lp = _prepared(lp, mode)
        h = layer_norm(x, lp["ln1"], lp["ln1_b"], eps)
        if kind == "mamba":
            o, M = mamba(h, lp, mode)
            shared = {**shared, "M": M}
        elif kind == "gmu":
            o = gmu(h, lp, shared["M"])
        elif kind == "cross":
            o = diff_attention(h, lp, shared["k"], shared["v"], l, eps)
        else:
            k, v = keys_values(h, lp)
            o = diff_attention(h, lp, k, v, l, eps,
                               spec["sliding_window"] if kind == "window"
                               else None)
            if kind == "full":
                shared = {**shared, "k": k, "v": v}
        x = x + o
        return x + mlp(layer_norm(x, lp["ln2"], lp["ln2_b"], eps), lp), shared


@functools.partial(jax.jit, static_argnames=("items", "mode"))
def _head_block(x, final_norm, final_norm_b, table, items, mode):
    """x [n, D], table [rows, D] (a block of the tied embedding)."""
    with jax.default_matmul_precision("highest"):
        x = layer_norm(x, final_norm.astype(jnp.float32),
                       final_norm_b.astype(jnp.float32),
                       dict(items)["layer_norm_eps"])
        # the head's matrix is the table transposed: its output columns
        # are the table's rows
        head = _rounded(table.T, mode).astype(jnp.float32)
        return x @ head


def hidden_states(params, tokens, spec, mode=None):
    """tokens [T] -> final hidden state [T, D] (before the last norm)."""
    items = spec_items(spec)
    n_layers = spec["num_hidden_layers"]
    x = params["embed"][tokens].astype(jnp.float32)
    D = x.shape[1]
    shared = {"M": jnp.zeros((x.shape[0], 0)), "k": jnp.zeros((0,)),
              "v": jnp.zeros((0,))}
    l = 0
    for segment in params["layers"]:
        repeats = jax.tree.leaves(segment)[0].shape[0]
        for rep in range(repeats):
            for stacked in segment:
                lp = jax.tree.map(lambda a: a[rep], stacked)
                x, shared = _layer(x, lp, shared, float(l),
                                   kind_of(l, n_layers), items, mode)
                l += 1
    assert l == n_layers and x.shape[1] == D
    return x


def logits_at(params, tokens, positions, spec, mode=None):
    """Float32 logits [len(positions), V] of one sequence at `positions`."""
    x = hidden_states(params, tokens, spec, mode)[positions]
    table = params["embed"]
    blocks = [
        _head_block(x, params["final_norm"], params["final_norm_b"],
                    table[i:i + VOCAB_BLOCK], spec_items(spec), mode)
        for i in range(0, table.shape[0], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)
