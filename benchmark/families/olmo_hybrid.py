"""The family of `olmo-hybrid-7b` (allenai/Olmo-Hybrid-7B): three layers of
gated delta-rule linear attention (a [96, 192] state matrix per head and
sequence, after a short convolution over q, k and v) to one of full
attention with no positional encoding and queries and keys normalised over
the whole projected vector; RMSNorm AFTER each sublayer (OLMo 2's order),
SwiGLU, an untied head. Its plain reference is
benchmark/reference/olmo_hybrid.py, which holds every equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves."""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import olmo_hybrid as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
# int8 / fp8: every matmul weight rounded; state-bf16: the delta-rule state
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
    """(heads, key size, value size) of a linear layer's state."""
    if spec["linear_num_key_heads"] != spec["linear_num_value_heads"]:
        raise ValueError("key heads shared by several value heads are not "
                         "written down here: the published model has one "
                         "key head a value head")
    return (spec["linear_num_value_heads"], spec["linear_key_head_dim"],
            spec["linear_value_head_dim"])


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig."""
    from ray_tpu.models import StackConfig

    if spec["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("the family's full-attention layers encode no "
                         "positions (rope_theta null)")
    heads, dk, dv = state_dims(spec)
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
        norm="rmsnorm", activation="swiglu", positional="none",
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec.get("torch_dtype", "bfloat16"),
        layer_kinds=kinds(spec), conv_taps=spec["linear_conv_kernel_dim"],
        qk_norm=True, qk_norm_whole=True, post_norm=True,
        gdn_heads=heads, gdn_key_dim=dk, gdn_value_dim=dv,
        gdn_neg_eigval=bool(spec["linear_allow_neg_eigval"]),
    )
    fields.update(overrides)
    return StackConfig(**fields)


STD = 0.02


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16, drawn by the benchmark:
    matrices normal(0.02) (output projections 0.02 / sqrt(2 L)), norm
    weights 1 + normal(0.02), the convolution's taps normal(0.5); the decay
    as its authors initialise it: A uniform in (0, 16] (`A_log` its
    logarithm), `dt_bias` the inverse softplus of a log-uniform step in
    [0.001, 0.1]. Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    out_std = STD / (2 * cfg.n_layers) ** 0.5

    def draw(k, name, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "d_A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1e-3, 16.0))
        elif name == "d_dt_b":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = jnp.log(jnp.expm1(step))
        elif name == "d_conv":
            w = n * 0.5
        elif init == "one":
            w = 1.0 + n * STD
        else:
            w = n * (out_std if init == "out" else STD)
        return w.astype(bf16)

    def layer(k, kind):
        shapes = stack.layer_shapes(cfg, kind)
        ks = jax.random.split(k, len(shapes))
        return {name: draw(ks[i], name, *shapes[name])
                for i, name in enumerate(sorted(shapes))}

    k_emb, k_norm, k_head, k_layers = jax.random.split(key, 4)
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
            "final_norm": draw(k_norm, "final_norm", (D,), "one"),
            "lm_head": draw(k_head, "lm_head", (D, V), "w")}


# -- operations and bytes, from shapes ---------------------------------------


def paged_decode(spec: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """One call of the paged decode-attention kernel (one attention layer,
    one step) whose sequences hold `context_tokens` cached tokens together:
    QK^T and PV as the algorithm needs them (one kv head a query head, not
    the whole row the kernel widens a query to), every key and value row
    read once."""
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  head_dim(spec))
    return {"flops": 2 * 2 * H * hd * context_tokens,
            "bytes": 2 * KVH * hd * BF16 * context_tokens}


def _recurrence(spec, tokens: float) -> Dict[str, float]:
    """The delta rule over `tokens` (position, sequence) pairs of one
    layer, from its equations: per head, token and state element one
    product for the decay, a product and a sum each for S'^T k, for the
    rank-one correction and for S^T q (7 dk dv); q, k, v, g and beta read
    and o written, in float32."""
    H, dk, dv = state_dims(spec)
    return {"flops": 7 * H * dk * dv * tokens,
            "bytes": H * (2 * dk + 2 * dv + 2) * F32 * tokens}


def gdn_chunk(spec: Dict[str, Any], tokens: float) -> Dict[str, float]:
    """One call of the prefill recurrence (one layer) over `tokens`
    positions: the state goes in and out once a call."""
    H, dk, dv = state_dims(spec)
    work = _recurrence(spec, tokens)
    work["bytes"] += 2 * H * dk * dv * F32
    return work


def gdn_step(spec: Dict[str, Any], slots: float) -> Dict[str, float]:
    """One call of the decode state update (one layer, one step) in which
    `slots` decode slots hold a LIVE sequence: each one's state read and
    written once. An empty slot counts nothing, so a kernel that passed
    over the whole array would read a low share."""
    H, dk, dv = state_dims(spec)
    work = _recurrence(spec, slots)
    work["bytes"] += 2 * H * dk * dv * F32 * slots
    return work


work = {"paged_decode": paged_decode, "gdn_chunk": gdn_chunk,
        "gdn_step": gdn_step}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step): the full-attention
    layers attend and hold a cache, the linear layers run the recurrence."""
    k = kinds(spec)
    return {"paged_decode": k.count("attn"), "gdn_chunk": k.count("gdn"),
            "gdn_step": k.count("gdn")}[group]


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=8,
              num_attention_heads=4, num_key_value_heads=4, vocab_size=256,
              max_position_embeddings=512, linear_num_key_heads=4,
              linear_num_value_heads=4, linear_key_head_dim=8,
              linear_value_head_dim=16)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Two whole periods of the layer pattern."""
    cut = {**spec, **SHRINK}
    cut["layer_types"] = spec["layer_types"][:cut["num_hidden_layers"]]
    return cut
