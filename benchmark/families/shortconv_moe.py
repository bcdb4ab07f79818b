"""The family of `lfm2-8b-a1b` (LiquidAI/LFM2-8B-A1B): a stack of gated
short convolutions with a GQA layer every third or fourth place (heads of
64, queries and keys RMS-normalised before the rotary turn), RMSNorm, two
leading dense layers and then 32 small experts, 4 a token, chosen by
sigmoid score plus a per-expert bias and weighted by the scores without it;
tied head. Its plain reference is benchmark/reference/lfm2.py, which holds
every equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import lfm2 as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
modes = ("int8", "fp8")   # every matmul weight rounded

# -- what the published config has no key for (each is in the configuration's
# file under `assumed`) ------------------------------------------------------


def head_dim(spec: Dict[str, Any]) -> int:
    return spec["hidden_size"] // spec["num_attention_heads"]


def kinds(spec: Dict[str, Any]):
    return tuple(ref.kind_of(l, spec)
                 for l in range(spec["num_hidden_layers"]))


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig. Dropless
    routing is capacity_factor = experts / selected (the program's capacity
    then equals the row's tokens, as for Mixtral)."""
    from ray_tpu.models import StackConfig

    experts, selected = spec["num_experts"], spec["num_experts_per_tok"]
    fields = dict(
        name=spec["model_type"],
        vocab_size=spec["vocab_size"],
        d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_dim=head_dim(spec),
        d_ff=spec["intermediate_size"],
        max_seq_len=spec["max_position_embeddings"],
        norm="rmsnorm", activation="swiglu", positional="rope",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["norm_eps"]),
        tie_embeddings=True,
        dtype=spec.get("torch_dtype", "bfloat16"),
        num_experts=experts, num_selected_experts=selected,
        capacity_factor=experts / selected, router_aux_coef=0.0,
        layer_kinds=kinds(spec), conv_taps=spec["conv_L_cache"],
        qk_norm=True, n_dense_layers=spec["num_dense_layers"],
        d_ff_expert=spec["moe_intermediate_size"],
        router="sigmoid" if spec["use_expert_bias"] else "softmax",
        norm_topk=bool(spec["norm_topk_prob"]),
        routed_scale=float(spec["routed_scaling_factor"]),
    )
    fields.update(overrides)
    return StackConfig(**fields)


STD = 0.02


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16, drawn by the benchmark:
    matrices normal(0.02) (output projections 0.02 / sqrt(2 L)), norm
    weights 1 + normal(0.02), the convolution's taps normal(0.5), the
    router normal(0.02) (scores then spread about 0.2 around a half) and its
    bias normal(0.05): nonzero, so that the choice and the weights differ.
    Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    out_std = STD / (2 * cfg.n_layers) ** 0.5

    def draw(k, name, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "c_conv":
            w = n * 0.5
        elif name == "router_bias":
            w = n * 0.05
        elif init == "one":
            w = 1.0 + n * STD
        else:
            w = n * (out_std if init == "out" else STD)
        return w.astype(bf16)

    def layer(k, kind, half):
        shapes = stack.layer_shapes(cfg, kind, half)
        ks = jax.random.split(k, len(shapes))
        return {name: draw(ks[i], name, *shapes[name])
                for i, name in enumerate(sorted(shapes))}

    k_emb, k_norm, k_layers = jax.random.split(key, 3)
    segments = []
    for first, period, repeats in cfg.segments():
        ks = jax.random.split(jax.random.fold_in(k_layers, first),
                              repeats * len(period))
        ks = ks.reshape(repeats, len(period), *ks.shape[1:])
        # one layer at a time: the f32 draws of a stacked segment of
        # experts would be gigabytes of temporaries
        segments.append(tuple(
            jax.lax.map(lambda k, kind=kind, half=cfg.second_halves[first + i]:
                        layer(k, kind, half), ks[:, i])
            for i, kind in enumerate(period)))
    D = cfg.d_model
    return {"embed": draw(k_emb, "embed", (cfg.vocab_size, D), "w"),
            "layers": segments,
            "final_norm": draw(k_norm, "final_norm", (D,), "one")}


# -- operations and bytes, from shapes ---------------------------------------


def paged_decode(spec: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """One call of the paged decode-attention kernel (one attention layer,
    one step) whose sequences hold `context_tokens` cached tokens together:
    QK^T and PV as the algorithm needs them (heads of 64, not the 128-lane
    tiles the kernel pads them to), every key and value row read once."""
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  head_dim(spec))
    return {"flops": 2 * 2 * H * hd * context_tokens,
            "bytes": 2 * KVH * hd * BF16 * context_tokens}


work = {"paged_decode": paged_decode}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step): only the attention
    layers attend and hold a cache, 3 of the 14 at the benchmark's depth."""
    return kinds(spec).count("attn")


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=10, num_attention_heads=8,
              num_key_value_heads=4, num_experts=8, num_experts_per_tok=2,
              vocab_size=256, max_position_embeddings=512)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Two dense layers and two whole periods of the layer pattern."""
    cut = {**spec, **SHRINK}
    cut["layer_types"] = spec["layer_types"][:cut["num_hidden_layers"]]
    return cut
