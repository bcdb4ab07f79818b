"""The family of `longcat-flash-omni` (the language model of
meituan-longcat/LongCat-Flash-Omni): a stack of DOUBLE layers, each two
latent attentions (MLA: 64 heads over one 512 + 64 latent row a token), two
dense SwiGLU FFNs and one shortcut expert layer whose router is a softmax
over 512 routed and 256 identity experts, 12 a token by score + bias,
weighted by the chosen scores times 6 and not renormalised; untied head.
No chip holds a layer's 512 experts: the configuration says which share of
them this chip holds, and program and reference compute that share's part.
Its plain reference is benchmark/reference/longcat_flash.py, which holds
every equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import longcat_flash as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
# every matmul weight rounded; or the router's scores in bfloat16
modes = ("int8", "fp8", "router-bf16")

LANES = 128


def latent_row(spec: Dict[str, Any]) -> int:
    """Lanes of a token's row in the pool as it is stored: the latent and
    the shared rotary key, padded to whole 128-lane tiles (the device's
    memory tiles the minor axis by 128 whether the program pads or not)."""
    used = spec["kv_lora_rank"] + spec["qk_rope_head_dim"]
    return -(-used // LANES) * LANES


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig: one "mla2"
    layer a published layer; `n_routed_experts` is what this chip HOLDS,
    `n_routed_experts_total` what the router chooses among. Dropless
    routing is capacity_factor = held experts / selected."""
    from ray_tpu.models import StackConfig

    held, selected = spec["n_routed_experts"], spec["moe_topk"]
    if spec["zero_expert_num"] and spec["zero_expert_type"] != "identity":
        raise ValueError(f"zero experts of type {spec['zero_expert_type']!r}")
    if spec["mla_scale_q_lora"] != spec["mla_scale_kv_lora"]:
        raise ValueError("the two mla_scale_* keys differ")
    fields = dict(
        name="longcat_flash",
        vocab_size=spec["vocab_size"],
        d_model=spec["hidden_size"],
        n_layers=spec["num_layers"],
        n_heads=spec["num_attention_heads"],
        d_ff=spec["ffn_hidden_size"],
        max_seq_len=spec["max_position_embeddings"],
        norm="rmsnorm", activation="swiglu", positional="none",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=False,
        dtype=spec.get("torch_dtype", "bfloat16"),
        num_experts=held, num_selected_experts=selected,
        capacity_factor=held / selected, router_aux_coef=0.0,
        layer_kinds=("mla2",) * spec["num_layers"],
        d_ff_expert=spec["expert_ffn_hidden_size"],
        router="softmax_all", norm_topk=False,
        routed_scale=float(spec["routed_scaling_factor"]),
        n_routed_experts=spec["n_routed_experts_total"],
        experts_first=spec["held_experts_first"],
        experts_zero=spec["zero_expert_num"],
        q_lora_rank=spec["q_lora_rank"], kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_dim=spec["qk_nope_head_dim"],
        qk_rope_dim=spec["qk_rope_head_dim"], v_head_dim=spec["v_head_dim"],
        mla_scale_lora=bool(spec["mla_scale_q_lora"]),
    )
    fields.update(overrides)
    return StackConfig(**fields)


STD = 0.02
# The router's bias against the scores' spread: with the router normal(0.02)
# over a normed stream of 6144 the logits spread 1.57, the 12th and 13th of
# 768 softmax scores lie near 0.0116 and 0.0006 apart (the twelve chosen
# sum to 0.27, 1.62 after the factor 6), and a bias of normal(0.002)
# changes 12.7% of the choices (0.001: 6.7%, 0.004: 26%; counted at these
# widths on 2048 random streams on the CPU, PR 39), so the choice and the
# weights differ.
BIAS_STD = 0.002


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats; a double layer's two blocks' leaves are
    named for the block, `wq_a0` / `wq_a1`), every leaf bf16, drawn by the
    benchmark:
    matrices normal(0.02) (output projections 0.02 / sqrt(2 x blocks)),
    norm weights 1 + normal(0.02), the router normal(0.02) and its bias
    normal(BIAS_STD). Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    out_std = STD / (2 * 2 * cfg.n_layers) ** 0.5

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


def _per_pair(spec: Dict[str, Any]) -> float:
    """Operations of one (query token, cached token) pair in the absorbed
    form, every head: a score against the row's kv_lora_rank + qk_rope
    lanes and a weighted sum of its kv_lora_rank lanes."""
    H, kl, R = (spec["num_attention_heads"], spec["kv_lora_rank"],
                spec["qk_rope_head_dim"])
    return 2 * H * (kl + R + kl)


def _queries_and_outputs(spec: Dict[str, Any], tokens: float) -> float:
    H = spec["num_attention_heads"]
    return H * (latent_row(spec) + spec["kv_lora_rank"]) * BF16 * tokens


def mla_decode(spec: Dict[str, Any], context_tokens: float,
               sequences: float = 0.0) -> Dict[str, float]:
    """One call of the latent decode kernel (one attention, one step) whose
    `sequences` live sequences hold `context_tokens` cached tokens
    together: each cached row read ONCE as it is stored (640 lanes), the
    sequences' queries read and outputs written. 109 operations a byte of
    row at 64 heads: under a v5e's ridge of 240, so memory-bound by the
    count, but nearer the ridge than any GQA decode (1 to 8)."""
    return {"flops": _per_pair(spec) * context_tokens,
            "bytes": latent_row(spec) * BF16 * context_tokens
            + _queries_and_outputs(spec, sequences)}


def mla_chunk(spec: Dict[str, Any], start: float, tokens: float) -> Dict[str, float]:
    """One call of the latent chunk kernel (one attention): `tokens`
    queries at positions start .. start + tokens - 1, query row r against
    the start + r + 1 cached rows it sees; the rows read once a call."""
    pairs = tokens * start + tokens * (tokens + 1) / 2
    return {"flops": _per_pair(spec) * pairs,
            "bytes": latent_row(spec) * BF16 * (start + tokens)
            + _queries_and_outputs(spec, tokens)}


work = {"mla_decode": mla_decode, "mla_chunk": mla_chunk}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step): two attentions a
    published layer. The decode kernel answers to `paged_decode` too: the
    accepted readers count a span's steps by that group's calls."""
    return 2 * spec["num_layers"]


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, ffn_hidden_size=128, expert_ffn_hidden_size=32,
              num_layers=2, num_attention_heads=4, q_lora_rank=32,
              kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16, n_routed_experts=4, n_routed_experts_total=8,
              held_experts_first=0, zero_expert_num=4, moe_topk=3,
              vocab_size=256, max_position_embeddings=512)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Two double layers; 4 of 8 routed experts held beside 4 identity
    experts, 3 a token."""
    return {**spec, **SHRINK}
