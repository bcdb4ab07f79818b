"""The plain reference of the Olmo Hybrid family (Olmo-Hybrid-7B, allenai):
every layer's equations in straightforward jax.numpy, float32, matmuls at
`highest` precision. No kernels, no cache, no pages, no state carried
between calls, nothing imported from the program.

    every layer l:  x = x + RMSNorm(Mix_l(x));  x = x + RMSNorm(FFN(x))
    (OLMo 2's reordered norm, arXiv:2501.00656: the norm follows the
    sublayer.) RMSNorm: x / sqrt(mean(x^2) + eps) * w, eps `rms_norm_eps`.
    FFN: W_down (silu(x W_gate) * x W_up), width `intermediate_size`.
    Mix_l by `layer_types[l]`:
      linear_attention  the gated delta rule (the FLA / Qwen3-Next form),
                        H = `linear_num_value_heads` heads, dk =
                        `linear_key_head_dim`, dv = `linear_value_head_dim`:
          [q~ ; k~ ; v~] = x W_in            (D -> H dk + H dk + H dv)
          each channel through a causal depthwise convolution of
          `linear_conv_kernel_dim` taps (zero before t = 0, no bias), then
          SiLU; per head q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(dk),
          k = k~ / sqrt(|k~|^2 + 1e-6)
          [a ; b] = x W_ab (D -> 2 H);  beta = 2 sigmoid(b) (the 2 is
          `linear_allow_neg_eigval`);  g = -exp(A_log) softplus(a + dt_bias)
          S_0 = 0 [dk, dv] per head, and a token at a time
              S' = exp(g_t) S_{t-1}
              S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
              o_t = S_t^T q_t
          mix = concat_h(RMSNorm_dv(o_t; w) * silu(x W_gate)) W_out
      full_attention    q = RMSNorm_{H hd}(x W_q), k = RMSNorm_{H hd}(x W_k)
                        (over the WHOLE projected vector, one weight a
                        lane), v = x W_v; H heads of hd, no positional
                        encoding (`rope_theta` null);
                        mix = softmax(q k^T / sqrt(hd), causal) v W_o
    Head: final RMSNorm, logits = x W_head (untied).

Departures from the published modeling code, none in the mathematics: the
projections are stored as the program's tree stores them (`d_in` [D, H (2 dk
+ dv)] in the order q, k, v; `d_conv` [K, channels] with tap K-1 on the
current position; `d_ab` [D, 2 H] in the order a, b; `wq` [D, H, hd]);
attention goes over blocks of queries and the head over blocks of the
vocabulary, so the reference fits beside 8.2 GB of bfloat16 weights. The
weights are the program's tree (`layers`: a list of segments, each a tuple
with one dict per layer of its period, stacked over repeats); the reference
walks it in order and tells a layer's kind by its index.

`mode` is the control's part: "int8" / "fp8" round every matmul weight (the
head too) per output column; "state-bf16" rounds the delta-rule state to
bfloat16 after every token (reported without a limit: the program's is
float32)."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.model import dense_ffn, quantize, rms_norm

Q_BLOCK = 256
VOCAB_BLOCK = 32768
MATMULS = frozenset(("d_in", "d_ab", "d_gate", "d_out", "wq", "wk", "wv",
                     "wo", "w_in", "w_gate", "w_out"))


def _rounded(w, mode):
    """A matmul weight through the control's precision ("state-bf16" is
    the state's control and leaves the weights)."""
    return quantize(w, None if mode == "state-bf16" else mode)


def static(spec: Dict[str, Any]):
    """What the equations read of the configuration, hashable for jit."""
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v) for k, v in spec.items()
        if isinstance(v, (int, float, bool, list))))


def kind_of(l: int, spec: Dict[str, Any]) -> str:
    return "gdn" if spec["layer_types"][l] == "linear_attention" else "attn"


def delta_rule(q, k, v, g, beta, mode=None):
    """q, k [T,H,dk]; v [T,H,dv]; g, beta [T,H] -> o [T,H,dv]: the
    recurrence as a plain scan from S_0 = 0."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, None, None] * S                     # [H,dk,dv]
        d = b_t[:, None] * (v_t - jnp.einsum("hij,hi->hj", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        if mode == "state-bf16":
            # not through a bfloat16 type: the TPU compiler elides the
            # convert pair as excess precision (chip, PR 34: the control
            # then read 6e-16); reduce_precision is never elided
            S = jax.lax.reduce_precision(S, 8, 7)
        return S, jnp.einsum("hij,hi->hj", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def linear_attention(x, lp, spec, mode=None):
    """x [T, D] -> [T, D]."""
    T = x.shape[0]
    H, dk, dv = (spec["linear_num_value_heads"], spec["linear_key_head_dim"],
                 spec["linear_value_head_dim"])
    K = spec["linear_conv_kernel_dim"]
    qkv = x @ lp["d_in"]
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, qkv.shape[1]), x.dtype), qkv], axis=0)
    conv = jnp.zeros_like(qkv)
    for j in range(K):  # tap K-1 multiplies the current position
        conv = conv + padded[j:j + T] * lp["d_conv"][j]
    qkv = jax.nn.silu(conv)

    def unit(u):
        return u / jnp.sqrt(jnp.sum(u * u, axis=-1, keepdims=True) + 1e-6)

    q = unit(qkv[:, :H * dk].reshape(T, H, dk)) / dk ** 0.5
    k = unit(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    ab = x @ lp["d_ab"]
    scale = 2.0 if spec["linear_allow_neg_eigval"] else 1.0
    beta = scale * jax.nn.sigmoid(ab[:, H:])
    g = -jnp.exp(lp["d_A_log"]) * jax.nn.softplus(ab[:, :H] + lp["d_dt_b"])
    o = delta_rule(q, k, v, g, beta, mode)
    o = rms_norm(o, lp["d_norm"], spec["rms_norm_eps"])
    gate = jax.nn.silu(x @ lp["d_gate"])
    return (o.reshape(T, H * dv) * gate) @ lp["d_out"]


def attention(x, lp, spec):
    T = x.shape[0]
    H = spec["num_attention_heads"]
    hd = spec["hidden_size"] // H
    eps = spec["rms_norm_eps"]

    def whole(u, w):  # RMSNorm over all the heads' lanes of a token
        flat = rms_norm(u.reshape(T, -1), w.reshape(-1), eps)
        return flat.reshape(u.shape)

    q = whole(jnp.einsum("td,dhk->thk", x, lp["wq"]), lp["q_norm"])
    k = whole(jnp.einsum("td,dhk->thk", x, lp["wk"]), lp["k_norm"])
    v = jnp.einsum("td,dhk->thk", x, lp["wv"])
    rep = H // spec["num_key_value_heads"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = min(Q_BLOCK, T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qhk,thk->hqt", qb, k) / hd ** 0.5
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v)

    o = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, H, hd)
    return jnp.einsum("thk,hkd->td", o, lp["wo"])


@functools.partial(jax.jit, static_argnames=("kind", "items", "mode"))
def _layer(x, lp, kind, items, mode):
    spec = dict(items)
    eps = spec["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        lp = {name: (_rounded(w, mode) if name in MATMULS else w)
              .astype(jnp.float32) for name, w in lp.items()}
        if kind == "gdn":
            mix = linear_attention(x, lp, spec, mode)
        else:
            mix = attention(x, lp, spec)
        x = x + rms_norm(mix, lp["ln1"], eps)
        ffn = dense_ffn(x, lp["w_in"], lp["w_gate"], lp["w_out"])
        return x + rms_norm(ffn, lp["ln2"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "mode"))
def _head_block(x, final_norm, head, eps, mode):
    """x [n, D], head [D, columns] (a block of the untied head)."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ _rounded(head, mode).astype(jnp.float32)


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
                x = _layer(x, lp, kind_of(l, spec), items, mode)
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
