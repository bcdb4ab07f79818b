"""A second family, which exists only as test data: GPT-2's block as the
program's `tiny-gpt2` has it (LayerNorm with biases, a GELU MLP with
biases, learned positions, a head tied to the embedding, attention without
biases), with its own plain reference in this file. It is no configuration
of the benchmark; benchmark/tests/test_families.py adds it from the files
under benchmark/tests/data/ alone, to show that the seam of
benchmark/families/ is whole (the contract: benchmark/families/mistral.py).

    x = wte[tokens] + wpe[positions]
    h = x + Attention(LayerNorm(x));  y = h + MLP(LayerNorm(h))
    Attention: causal softmax(q k^T / sqrt(head_dim)) v, every head its own
               keys and values
    MLP: w_out(gelu_tanh(x w_in + b_in)) + b_out
    logits = LayerNorm(y_last) wte^T

The reference is float32 at `highest` precision, no kernels, no cache,
nothing imported from the program; `mode` rounds every matmul weight (and
the tied head) to int8 or fp8 and back, scaled per output column."""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

BF16 = 2  # bytes
STD = 0.1
PAD_TO = 32
modes = ("int8", "fp8")


# -- the plain reference -----------------------------------------------------


def quantize(w, mode):
    if mode is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)),
                   keepdims=True) + 1e-30
    if mode == "int8":
        scale = amax / 127.0
        return jnp.round(w / scale) * scale
    if mode == "fp8":
        scale = amax / 448.0
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown control precision {mode!r}")


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh((2 / jnp.pi) ** 0.5 * (x + 0.044715 * x ** 3)))


def block(x, lp, eps, mode):
    T = x.shape[0]
    hd = lp["wq"].shape[-1]
    wq, wk, wv, wo, w_in, w_out = (quantize(lp[n], mode) for n in (
        "wq", "wk", "wv", "wo", "w_in", "w_out"))
    h = layer_norm(x, lp["ln1"], lp["ln1_b"], eps)
    q, k, v = (jnp.einsum("td,dhk->thk", h, w) for w in (wq, wk, wv))
    s = jnp.einsum("qhk,thk->hqt", q, k) / hd ** 0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    x = x + jnp.einsum("hqt,thk,hkd->qd", p, v, wo)
    h = layer_norm(x, lp["ln2"], lp["ln2_b"], eps)
    return x + gelu_tanh(h @ w_in + lp["b_in"]) @ w_out + lp["b_out"]


@functools.partial(jax.jit, static_argnames=("layers", "eps", "mode"))
def _logits_at(params, tokens, at, layers, eps, mode):
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens] + params["pos_emb"][: tokens.shape[0]]
        for i in range(layers):
            x = block(x, jax.tree.map(lambda a: a[i], params["layers"]),
                      eps, mode)
        x = layer_norm(x[at], params["final_norm"], params["final_norm_b"], eps)
        return x @ quantize(params["embed"].T, mode)


def logits_at(params, tokens, at, spec, mode=None):
    """Float32 logits [len(at), V] of one sequence at the positions `at`."""
    return _logits_at(params, tokens, at, spec["n_layer"],
                      spec["layer_norm_epsilon"], mode)


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    from ray_tpu.models import ModelConfig

    fields = dict(
        name=spec["model_type"], vocab_size=spec["vocab_size"],
        d_model=spec["n_embd"], n_layers=spec["n_layer"],
        n_heads=spec["n_head"], d_ff=spec["n_inner"],
        max_seq_len=spec["n_positions"],
        norm="layernorm", activation="gelu", positional="learned",
        norm_eps=float(spec["layer_norm_epsilon"]), tie_embeddings=True,
        dtype=spec["torch_dtype"])
    fields.update(overrides)
    return ModelConfig(**fields)


def init_weights(spec: Dict[str, Any], key):
    """The tree the program's `tiny-gpt2` has, every leaf bf16; the biases
    are drawn too, so that one left out shows. Traceable."""
    D, F, L = spec["n_embd"], spec["n_inner"], spec["n_layer"]
    H, V = spec["n_head"], spec["vocab_size"]
    hd = D // H
    out_std = STD / (2 * L) ** 0.5
    bf16 = jnp.bfloat16

    def dense(k, shape, std=STD):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(bf16)

    def layer(k):
        ks = jax.random.split(k, 10)
        return {"ln1": jnp.ones((D,), bf16), "ln1_b": dense(ks[0], (D,)),
                "ln2": jnp.ones((D,), bf16), "ln2_b": dense(ks[1], (D,)),
                "wq": dense(ks[2], (D, H, hd)), "wk": dense(ks[3], (D, H, hd)),
                "wv": dense(ks[4], (D, H, hd)),
                "wo": dense(ks[5], (H, hd, D), out_std),
                "w_in": dense(ks[6], (D, F)), "b_in": dense(ks[7], (F,)),
                "w_out": dense(ks[8], (F, D), out_std),
                "b_out": dense(ks[9], (D,))}

    k_emb, k_pos, k_norm, k_layers = jax.random.split(key, 4)
    return {"embed": dense(k_emb, (V, D)),
            "pos_emb": dense(k_pos, (spec["n_positions"], D), STD / 2),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, L)),
            "final_norm": jnp.ones((D,), bf16),
            "final_norm_b": dense(k_norm, (D,))}


# -- operations and bytes, from shapes ---------------------------------------


def paged_decode(spec: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    D = spec["n_embd"]  # heads x head size, for queries, keys and values
    return {"flops": 2 * 2 * D * context_tokens,
            "bytes": 2 * D * BF16 * context_tokens}


work = {"paged_decode": paged_decode}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    return spec["n_layer"]


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration of the test data is tiny as it stands."""
    return dict(spec)
