"""The family of `kanana-2-30b-a3b` (kakaocorp/kanana-2-30b-a3b-instruct-2601,
`model_type` deepseek_v3: the DeepSeek-V3 block): a stack of layers, each
ONE latent attention (MLA: 32 heads over one 512 + 64 latent row a token,
queries projected directly) and one second half: a leading dense SwiGLU,
then 128 small experts, 6 a token by sigmoid score + bias, renormalised and
scaled, ALL held, beside two shared experts that every token passes
through; untied head. Its plain reference is benchmark/reference/kanana.py,
which holds every equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves. The latent
kernels are the ones `longcat-flash-omni` runs, at 32 query rows a sequence
where that family has 64; their operations and bytes are counted by that
family's functions from this configuration's own keys."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families import mla_shortcut_moe as latent
from benchmark.reference import kanana as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
# every matmul weight rounded; or the router's scores in bfloat16
modes = ("int8", "fp8", "router-bf16")

# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig: one "mla"
    layer a published layer, `first_k_dense_replace` leading dense ones, the
    shared experts as ONE gated FFN of `n_shared_experts` x
    `moe_intermediate_size`. Dropless routing is capacity_factor = experts
    / selected."""
    from ray_tpu.models import StackConfig

    for key, only in (("q_lora_rank", None), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"), ("moe_layer_freq", 1),
                      ("rope_interleave", True), ("attention_bias", False),
                      ("hidden_act", "silu")):
        if spec[key] != only:
            raise ValueError(f"{key} {spec[key]!r}: this family is written "
                             f"for {only!r}")
    experts, selected = spec["n_routed_experts"], spec["num_experts_per_tok"]
    fields = dict(
        name=spec["model_type"],
        vocab_size=spec["vocab_size"],
        d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        d_ff=spec["intermediate_size"],
        max_seq_len=spec["max_position_embeddings"],
        norm="rmsnorm", activation="swiglu", positional="none",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec.get("torch_dtype", "bfloat16"),
        num_experts=experts, num_selected_experts=selected,
        capacity_factor=experts / selected, router_aux_coef=0.0,
        layer_kinds=("mla",) * spec["num_hidden_layers"],
        n_dense_layers=spec["first_k_dense_replace"],
        d_ff_expert=spec["moe_intermediate_size"],
        d_ff_shared=spec["n_shared_experts"] * spec["moe_intermediate_size"],
        router="sigmoid", norm_topk=bool(spec["norm_topk_prob"]),
        routed_scale=float(spec["routed_scaling_factor"]),
        kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_dim=spec["qk_nope_head_dim"],
        qk_rope_dim=spec["qk_rope_head_dim"], v_head_dim=spec["v_head_dim"],
    )
    fields.update(overrides)
    return StackConfig(**fields)


STD = 0.02
# The router's bias against the scores' spread: with the router normal(0.02)
# over a normed stream of 2048 the logits spread 0.90 and the sigmoid scores
# 0.19 about 0.5; the 6th and 7th of 128 lie near 0.82 and 0.011 apart (the
# six chosen sum to 5.2), and a bias of normal(0.02) changes 14.3% of the
# choices (0.005: 3.9%, 0.01: 7.9%, 0.04: 27.8%; counted at these widths on
# 2048 random streams on the CPU, PR 48), so the choice and the weights
# differ.
BIAS_STD = 0.02


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16, drawn by the benchmark:
    matrices normal(0.02), output projections 0.02 / sqrt(2 x the PUBLISHED
    depth), norm weights 1 + normal(0.02), the router normal(0.02) and its
    bias normal(BIAS_STD). Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    depth = spec.get("published", {}).get("num_hidden_layers", cfg.n_layers)
    out_std = STD / (2 * depth) ** 0.5

    def draw(k, name, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "router_bias":
            w = n * BIAS_STD
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

    k_emb, k_norm, k_head, k_layers = jax.random.split(key, 4)
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
    D, V = cfg.d_model, cfg.vocab_size
    return {"embed": draw(k_emb, "embed", (V, D), "w"),
            "layers": segments,
            "final_norm": draw(k_norm, "final_norm", (D,), "one"),
            "lm_head": draw(k_head, "lm_head", (D, V), "w")}


# -- operations and bytes, from the equations --------------------------------

# the absorbed form's count is the kernels', whatever the heads: 2 x 32 x
# (576 + 512) operations a cached token for 1280 bytes of row, 54 a byte
work = {"mla_decode": latent.mla_decode, "mla_chunk": latent.mla_chunk}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step): one attention a layer.
    The decode kernel answers to `paged_decode` too, as in the other latent
    family."""
    return spec["num_hidden_layers"]


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=4, num_attention_heads=4, head_dim=16,
              num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
              qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
              n_routed_experts=8, num_experts_per_tok=3, vocab_size=256,
              max_position_embeddings=512)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """One dense layer and three expert layers of 8 experts top 3 beside the
    shared pair."""
    return {**spec, **SHRINK}
