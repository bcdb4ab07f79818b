"""The family of `granite-4.0-h-micro` (ibm-granite; `model_type`
granitemoehybrid with `num_local_experts` 0): Mamba-2 layers (64 heads with
ONE scalar decay each and a [64, 128] state matrix a head and sequence, B
and C shared by the heads of a group, a short convolution over x, B and C
together, a gated RMSNorm before the out-projection) beside a few layers of
GQA with no positional encoding; RMSNorm before each sublayer, a dense
SwiGLU in every layer, a tied table, and four scalars (on the embedding,
the attention scores, both sublayers' outputs and the logits). Its plain
reference is benchmark/reference/granite_hybrid.py, which holds every
equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves."""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import granite_hybrid as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
# int8 / fp8: every matmul weight rounded; state-bf16: the state-space state
# kept in bfloat16 (reported without a limit: the program's is float32)
modes = ("int8", "fp8", "state-bf16")

# -- what the published config has no key for (each is in the configuration's
# file under `assumed`) ------------------------------------------------------

F32 = 4


def head_dim(spec: Dict[str, Any]) -> int:
    return spec["hidden_size"] // spec["num_attention_heads"]


def kinds(spec: Dict[str, Any]):
    return tuple(ref.kind_of(l, spec)
                 for l in range(spec["num_hidden_layers"]))


def state_dims(spec: Dict[str, Any]):
    """(heads, head size, state size, groups) of a Mamba-2 layer's state."""
    heads, size = spec["mamba_n_heads"], spec["mamba_d_head"]
    if heads * size != spec["mamba_expand"] * spec["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand x "
                         "hidden_size")
    return heads, size, spec["mamba_d_state"], spec["mamba_n_groups"]


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig."""
    from ray_tpu.models import StackConfig

    if (spec["num_local_experts"] or spec["position_embedding_type"] != "nope"
            or spec["attention_bias"] or spec["mamba_proj_bias"]
            or not spec["mamba_conv_bias"]
            or spec["normalization_function"] != "rmsnorm"
            or spec["hidden_act"] != "silu"):
        raise ValueError("the family is written for the dense member: no "
                         "experts, no positions, RMSNorm, SiLU, a bias on "
                         "the convolution alone")
    heads, size, d_state, groups = state_dims(spec)
    fields = dict(
        name=spec["model_type"],
        vocab_size=spec["vocab_size"],
        d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_dim=head_dim(spec),
        d_ff=spec["shared_intermediate_size"],
        max_seq_len=spec["max_position_embeddings"],
        norm="rmsnorm", activation="swiglu", positional="none",
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec.get("torch_dtype", "bfloat16"),
        layer_kinds=kinds(spec), ssm_inner=heads * size, ssm_state=d_state,
        ssm_conv=spec["mamba_d_conv"], ssm_heads=heads, ssm_groups=groups,
        embedding_multiplier=float(spec["embedding_multiplier"]),
        attention_multiplier=float(spec["attention_multiplier"]),
        residual_multiplier=float(spec["residual_multiplier"]),
        logits_scaling=float(spec["logits_scaling"]),
    )
    fields.update(overrides)
    return StackConfig(**fields)


# the recipe's three numbers that are no scale of a width (each is in the
# configuration's file under `assumed`, with its reason)
SCORE_STD = 1.5          # of an attention score, multiplier included
STEP = (0.05, 1.0)       # the state-space step that `s_dt_b` alone gives
TAP_STD = 0.5


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16, drawn by the benchmark
    so that the 40 layers, and not the embedding, make the stream that the
    head reads (a limit on the logits then sees every layer):

    - matrices normal(1 / sqrt(fan-in)), 0.0221 at the published width (the
      table too): a unit input gives a unit output at any width;
    - output projections normal(1 / (residual_multiplier sqrt(fan-in))):
      the multiplier is the depth's scale, so the draw does not scale by
      the depth a second time (0.02 / sqrt(2 L)), and a sublayer adds about
      what it is given: with both scales each adds a twelfth of the
      embedding times 12, and the last token is its own successor;
    - `wq` and `wk` so that a score, `attention_multiplier` included, has
      the standard deviation SCORE_STD (the multiplier is 1 / 64 where 1 /
      sqrt(head size) is 1 / 8: unit q and k would give scores of 0.1,
      every softmax flat);
    - norm weights 1 + normal(0.02), the convolution's taps normal(0.5)
      and its bias normal(0.02);
    - the decay: A uniform in [1, 16] (`s_A_log` its logarithm) and D ones
      as the layer's authors initialise them; `s_dt_b` the inverse softplus
      of a log-uniform step in STEP, not the authors' [0.001, 0.1], under
      which D x is nine tenths of a mixer's output and the carried state
      does not reach the logits (trained steps spread as widely).

    Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    qk_gain = (SCORE_STD / (cfg.attention_multiplier
                            * cfg.head_dim ** 0.5)) ** 0.5

    def draw(k, name, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "s_A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "s_dt_b":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, *(math.log(s) for s in STEP)))
            w = jnp.log(jnp.expm1(step))
        elif name == "s_D":
            w = jnp.ones(shape, jnp.float32)
        elif name == "s_conv":
            w = n * TAP_STD
        elif name == "s_conv_b":
            w = n * 0.02
        elif init == "one":
            w = 1.0 + n * 0.02
        elif init == "out":
            w = n / (cfg.residual_multiplier * math.prod(shape[:-1]) ** 0.5)
        else:  # [fan-in, ...] (the table [V, D] is the head's [D, V])
            fan_in = shape[-1] if name == "embed" else shape[0]
            w = n * (qk_gain if name in ("wq", "wk") else 1.0) / fan_in ** 0.5
        return w.astype(bf16)

    def layer(k, kind):
        shapes = stack.layer_shapes(cfg, kind)
        ks = jax.random.split(k, len(shapes))
        return {name: draw(ks[i], name, *shapes[name])
                for i, name in enumerate(sorted(shapes))}

    k_emb, k_norm, k_layers = jax.random.split(key, 3)
    segments = []
    for first, period, repeats in cfg.segments():
        ks = jax.random.split(jax.random.fold_in(k_layers, first),
                              repeats * len(period))
        ks = ks.reshape(repeats, len(period), *ks.shape[1:])
        # one layer at a time: the f32 draws of a stacked segment would be
        # gigabytes of temporaries
        segments.append(tuple(
            jax.lax.map(lambda k, kind=kind: layer(k, kind), ks[:, i])
            for i, kind in enumerate(period)))
    D, V = cfg.d_model, cfg.vocab_size
    return {"embed": draw(k_emb, "embed", (V, D), "w"),
            "layers": segments,
            "final_norm": draw(k_norm, "final_norm", (D,), "one")}


# -- operations and bytes, from shapes ---------------------------------------


def paged_decode(spec: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """One call of the paged decode-attention kernel (one attention layer,
    one step) whose sequences hold `context_tokens` cached tokens together:
    QK^T and PV as the algorithm needs them (one kv head a group of query
    heads, not the whole row the kernel widens a query to), every key and
    value row read once."""
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  head_dim(spec))
    return {"flops": 2 * 2 * H * hd * context_tokens,
            "bytes": 2 * KVH * hd * BF16 * context_tokens}


def _operands(spec) -> float:
    """Bytes a token's x, dt, B and C in and y out are, in float32."""
    H, P, N, G = state_dims(spec)
    return (2 * H * P + H + 2 * G * N) * F32


def ssd_step(spec: Dict[str, Any], slots: float) -> Dict[str, float]:
    """One call of the decode state update (one layer, one step) in which
    `slots` decode slots hold a LIVE sequence: each one's state read and
    written once, its operands beside it; per state element a product for
    the decay, a product and a sum for the rank-one input and for the
    output (5 H P N). An empty slot counts nothing, so a kernel that passed
    over the whole array would read a low share."""
    H, P, N, _ = state_dims(spec)
    return {"flops": 5 * H * P * N * slots,
            "bytes": (2 * H * P * N * F32 + _operands(spec)) * slots}


def ssd_chunk(spec: Dict[str, Any], tokens: float) -> Dict[str, float]:
    """One call of the prefill recurrence (one layer) over `tokens`
    positions, from the dual form's products in blocks of
    `mamba_chunk_size` positions L, the causal half of what is square (as
    the flash kernels' pairs are counted): per token C B^T over (L + 1) / 2
    earlier positions a group (2 N each), that row against dt x (2 H P
    each), C against the carried state and the block's own share of the
    next (2 N H P each). The operands in float32 and the state in and out
    once a call."""
    H, P, N, G = state_dims(spec)
    seen = (spec["mamba_chunk_size"] + 1) / 2
    return {"flops": (2 * seen * (G * N + H * P) + 4 * N * H * P) * tokens,
            "bytes": _operands(spec) * tokens + 2 * H * P * N * F32}


work = {"paged_decode": paged_decode, "ssd_chunk": ssd_chunk,
        "ssd_step": ssd_step}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step): the attention layers
    attend and hold a cache, the Mamba-2 layers run the recurrence."""
    k = kinds(spec)
    return {"paged_decode": k.count("attn"), "ssd_chunk": k.count("ssd"),
            "ssd_step": k.count("ssd")}[group]


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, intermediate_size=128,
              shared_intermediate_size=128, num_hidden_layers=8,
              num_attention_heads=8, num_key_value_heads=2, vocab_size=256,
              max_position_embeddings=512, mamba_n_heads=8, mamba_d_head=16,
              mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=16)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """The first eight layers of the pattern: attention at 5; two groups,
    so that a group's B and C are not every head's."""
    cut = {**spec, **SHRINK}
    cut["layer_types"] = spec["layer_types"][:cut["num_hidden_layers"]]
    return cut
