"""The plain reference of the Granite 4.0-H family (granite-4.0-h-micro,
ibm-granite; `model_type` granitemoehybrid with no experts): every layer's
equations in straightforward jax.numpy, float32, matmuls at `highest`
precision. No kernels, no cache, no pages, no state carried between calls,
no chunking of the recurrence, nothing imported from the program.

    x = `embedding_multiplier` * E[token]
    every layer l, N an RMSNorm (x / sqrt(mean(x^2) + eps) * w, eps
    `rms_norm_eps`), r = `residual_multiplier`:
        x = x + r * Mix_l(N1(x));  x = x + r * MLP(N2(x))
    MLP: W_down (silu(h W_gate) * h W_up), width `shared_intermediate_size`
    (`num_local_experts` 0: no router, no experts).
    Mix_l by `layer_types[l]`:
      mamba      Mamba-2 (the `transformers` GraniteMoeHybrid / Bamba form),
                 H = `mamba_n_heads` heads of P = `mamba_d_head`, d_inner =
                 H P = `mamba_expand` x hidden, N = `mamba_d_state`, G =
                 `mamba_n_groups` groups of heads that share B and C:
          [z ; xBC] = h W_in   (D -> d_inner + (d_inner + 2 G N), no bias)
          dt~ = h W_dt         (D -> H, no bias)
          xBC = silu(conv(xBC) + bias): a causal depthwise convolution of
                `mamba_d_conv` taps a channel, zero before t = 0
          [x ; B ; C] = xBC    (d_inner | G N | G N)
          dt = softplus(dt~ + dt_bias);  a = exp(dt A), A = -exp(A_log): ONE
          scalar a head and token (nothing clamps dt)
          S_0 = 0 [P, N] per head, and a token at a time
              S_t = a_t S_{t-1} + (dt_t x_t) B_t^T
              y_t = S_t C_t + D x_t
          y = N_g(y * silu(z)): the gate FIRST, then an RMSNorm over the
              d_inner / G lanes of each group, one weight a lane
          mix = y W_out        (no bias)
      attention  GQA, `num_attention_heads` query heads over
                 `num_key_value_heads` kv heads of hidden / heads, no bias,
                 NO positional encoding (`position_embedding_type` nope);
                 mix = softmax(q k^T * `attention_multiplier`, causal) v W_o
                 (the multiplier is NOT 1 / sqrt(head size))
    Head: final RMSNorm, logits = (x E^T) / `logits_scaling` (tied table).

Departures from the published modeling code, none in the mathematics: the
in-projection is stored as the program's tree stores it, in two leaves
(`s_in` [D, d_inner + d_inner + 2 G N] in the order z, x, B, C; `s_dt`
[D, H]); `s_conv` is [K, channels] with tap K-1 on the current position;
`wq` is [D, H, hd]; attention goes over blocks of queries and the head over
blocks of the vocabulary, so the reference fits beside 6.4 GB of bfloat16
weights. The weights are the program's tree (`layers`: a list of segments,
each a tuple with one dict per layer of its period, stacked over repeats);
the reference walks it in order and tells a layer's kind by its index.

`mode` is the control's part: "int8" / "fp8" round every matmul weight (the
tied table where it is the head's, too) per output column; "state-bf16"
rounds the state-space state to bfloat16 after every token (reported
without a limit: the program's is float32)."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmark.reference.model import dense_ffn, quantize, rms_norm

Q_BLOCK = 256
VOCAB_BLOCK = 32768
MATMULS = frozenset(("s_in", "s_dt", "s_out", "wq", "wk", "wv", "wo",
                     "w_in", "w_gate", "w_out"))


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
    return "ssd" if spec["layer_types"][l] == "mamba" else "attn"


def recurrence(x, dt, A, Bm, Cm, D, mode=None):
    """x [T,H,P]; dt [T,H]; A, D [H]; Bm, Cm [T,G,N] -> y [T,H,P]: the
    recurrence as a plain scan from S_0 = 0."""
    H, P = x.shape[1:]
    G, N = Bm.shape[1:]

    def step(S, xs):
        x_t, dt_t, b_t, c_t = xs
        b_h = jnp.repeat(b_t, H // G, axis=0)                   # [H,N]
        c_h = jnp.repeat(c_t, H // G, axis=0)
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        if mode == "state-bf16":
            # not through a bfloat16 type: the TPU compiler elides the
            # convert pair as excess precision; reduce_precision is never
            # elided
            S = jax.lax.reduce_precision(S, 8, 7)
        return S, jnp.einsum("hpn,hn->hp", S, c_h) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, Bm, Cm))
    return y


def mamba2(h, lp, spec, mode=None):
    """h [T, D] -> [T, D]."""
    T = h.shape[0]
    H, P, N, G, K = (spec["mamba_n_heads"], spec["mamba_d_head"],
                     spec["mamba_d_state"], spec["mamba_n_groups"],
                     spec["mamba_d_conv"])
    Di = H * P
    zxbc = h @ lp["s_in"]
    z, xbc = zxbc[:, :Di], zxbc[:, Di:]
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, xbc.shape[1]), h.dtype), xbc], axis=0)
    conv = jnp.zeros_like(xbc) + lp["s_conv_b"]
    for j in range(K):  # tap K-1 multiplies the current position
        conv = conv + padded[j:j + T] * lp["s_conv"][j]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :Di].reshape(T, H, P)
    Bm = xbc[:, Di:Di + G * N].reshape(T, G, N)
    Cm = xbc[:, Di + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(h @ lp["s_dt"] + lp["s_dt_b"])
    y = recurrence(x, dt, -jnp.exp(lp["s_A_log"]), Bm, Cm, lp["s_D"], mode)
    y = (y.reshape(T, Di) * jax.nn.silu(z)).reshape(T, G, Di // G)
    y = rms_norm(y, lp["s_norm"].reshape(G, Di // G), spec["rms_norm_eps"])
    return y.reshape(T, Di) @ lp["s_out"]


def attention(h, lp, spec):
    T = h.shape[0]
    H, KVH = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["hidden_size"] // H
    q = jnp.einsum("td,dhk->thk", h, lp["wq"])
    k = jnp.repeat(jnp.einsum("td,dhk->thk", h, lp["wk"]), H // KVH, axis=1)
    v = jnp.repeat(jnp.einsum("td,dhk->thk", h, lp["wv"]), H // KVH, axis=1)
    block = min(Q_BLOCK, T)

    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        s = jnp.einsum("qhk,thk->hqt", qb, k) * spec["attention_multiplier"]
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", p, v)

    o = jax.lax.map(one_block, jnp.arange(0, T, block)).reshape(T, H, hd)
    return jnp.einsum("thk,hkd->td", o, lp["wo"])


@functools.partial(jax.jit, static_argnames=("kind", "items", "mode"))
def _layer(x, lp, kind, items, mode):
    spec = dict(items)
    eps, r = spec["rms_norm_eps"], spec["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        lp = {name: (_rounded(w, mode) if name in MATMULS else w)
              .astype(jnp.float32) for name, w in lp.items()}
        h = rms_norm(x, lp["ln1"], eps)
        mix = mamba2(h, lp, spec, mode) if kind == "ssd" \
            else attention(h, lp, spec)
        x = x + r * mix
        h = rms_norm(x, lp["ln2"], eps)
        return x + r * dense_ffn(h, lp["w_in"], lp["w_gate"], lp["w_out"])


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "mode"))
def _head_block(x, final_norm, rows, eps, scaling, mode):
    """x [n, D], rows [columns, D] (a block of the tied table)."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(x, final_norm.astype(jnp.float32), eps)
        return x @ _rounded(rows.T, mode).astype(jnp.float32) / scaling


def hidden_states(params, tokens, spec, mode=None):
    """tokens [T] -> final hidden state [T, D] (before the last norm)."""
    if not spec["tie_word_embeddings"] or spec["num_local_experts"]:
        raise ValueError("written for the tied, dense member of the family")
    items = static(spec)
    x = spec["embedding_multiplier"] * params["embed"][tokens].astype(
        jnp.float32)
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
    table = params["embed"]
    blocks = [_head_block(x, params["final_norm"], table[i:i + VOCAB_BLOCK],
                          spec["rms_norm_eps"], float(spec["logits_scaling"]),
                          mode)
              for i in range(0, table.shape[0], VOCAB_BLOCK)]
    return jnp.concatenate(blocks, axis=-1)
