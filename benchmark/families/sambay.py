"""The family of `phi-4-mini-flash` (SambaY, arXiv:2507.06607): a stack of
unlike layers, Mamba / window-attention pairs, one Mamba and one
full-attention layer in the middle, then gated-memory / cross-attention
pairs that read the middle's scan output and its one KV cache; LayerNorm,
differential attention, no positional encoding, tied head. Its plain
reference is benchmark/reference/sambay.py, which holds every equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves."""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import sambay as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
# int8 / fp8: every matmul weight rounded; state-bf16: the scan state kept
# in bfloat16 (reported without a limit: the program's is float32)
modes = ("int8", "fp8", "state-bf16")

# -- what the published config has no key for (each is in the configuration's
# file under `assumed`) ------------------------------------------------------

F32 = 4


def inner(spec: Dict[str, Any]) -> int:
    return 2 * spec["hidden_size"]        # Mamba / GMU inner width


def dt_rank(spec: Dict[str, Any]) -> int:
    return spec["hidden_size"] // 16


def head_dim(spec: Dict[str, Any]) -> int:
    return spec["hidden_size"] // spec["num_attention_heads"]


STATE, CONV = 16, 4


def kinds(spec: Dict[str, Any]):
    n = spec["num_hidden_layers"]
    return tuple(ref.kind_of(l, n) for l in range(n))


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig."""
    from ray_tpu.models import StackConfig

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
        norm="layernorm", activation="swiglu", positional="none",
        norm_eps=float(spec["layer_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec.get("torch_dtype", "bfloat16"),
        layer_kinds=kinds(spec), window=spec["sliding_window"],
        ssm_inner=spec.get("mamba_d_inner", inner(spec)),
        ssm_state=spec.get("mamba_d_state", STATE),
        ssm_conv=spec.get("mamba_d_conv", CONV),
        ssm_dt_rank=spec.get("mamba_dt_rank", dt_rank(spec)),
    )
    fields.update(overrides)
    return StackConfig(**fields)


STD = 0.02


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16, drawn by the benchmark:
    matrices normal(0.02) (output projections 0.02 / sqrt(2 L)), biases
    normal(0.02), norm weights 1 + normal(0.02), lambda vectors normal(0.1);
    Mamba as its authors initialise it: A = -(1..N), D = 1, the step bias
    the inverse softplus of a log-uniform step in [0.001, 0.1], the step
    projection normal(rank ** -0.5), the conv taps normal(0.5).
    Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    out_std = STD / (2 * cfg.n_layers) ** 0.5

    def draw(k, name, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "m_A_log":
            w = jnp.log(jnp.broadcast_to(
                jnp.arange(1, shape[0] + 1, dtype=jnp.float32)[:, None], shape))
        elif name == "m_dt_b":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = jnp.log(jnp.expm1(step))
        elif name == "m_D":
            w = jnp.ones(shape, jnp.float32)
        elif name == "m_dt":
            w = n * shape[0] ** -0.5
        elif name == "m_conv":
            w = n * 0.5
        elif name.startswith("lam_"):
            w = n * 0.1
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
    D = cfg.d_model
    return {"embed": draw(k_emb, "embed", (cfg.vocab_size, D), "w"),
            "layers": segments,
            "final_norm": draw(k_norm, "final_norm", (D,), "one"),
            "final_norm_b": draw(jax.random.fold_in(k_norm, 1),
                                 "final_norm_b", (D,), "zero")}


# -- operations and bytes, from shapes ---------------------------------------


def _pairs(spec):
    """(query heads as the kernels see them, KV rows, row width): a
    differential pair [k1 ; k2] is one row of twice the head size."""
    return (spec["num_attention_heads"], spec["num_key_value_heads"] // 2,
            2 * head_dim(spec))


def paged_decode(spec: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """One call of a paged decode-attention kernel (one layer, one step)
    over `context_tokens` cached tokens that it reads: every KV row once,
    keys and values; both softmaxes' products as the algorithm needs them
    (head_dim wide each for QK^T, the pair's width for PV)."""
    H, rows, width = _pairs(spec)
    hd = head_dim(spec)
    return {"flops": 2 * H * (hd + width) * context_tokens,
            "bytes": 2 * rows * width * BF16 * context_tokens}


def ssm_scan(spec: Dict[str, Any], tokens: float) -> Dict[str, float]:
    """One call of the selective scan (one layer) over `tokens` positions:
    per position, state row and channel: exp, two products and a sum for
    the state, a product and a sum for the output. Reads u and dt, writes
    y (float32), reads B and C; the state goes in and out once."""
    Di, N = inner(spec), STATE
    return {"flops": 6 * N * Di * tokens,
            "bytes": (3 * Di + 2 * N) * F32 * tokens + 2 * N * Di * F32}


def ssm_step(spec: Dict[str, Any], slots: float) -> Dict[str, float]:
    """One call of the decode state update (one layer, one step) over
    `slots` decode slots: each slot's state read and written once."""
    Di, N = inner(spec), STATE
    return {"flops": 6 * N * Di * slots,
            "bytes": (2 * N * Di + 3 * Di + 2 * N) * F32 * slots}


work = {"paged_decode": paged_decode, "paged_decode_window": paged_decode,
        "ssm_scan": ssm_scan, "ssm_step": ssm_step}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step). `paged_decode` is every
    paged decode-attention call, windowed or not: the window layers, the
    full layer and the cross layers that read its cache."""
    k = kinds(spec)
    attends = {"window": k.count("window"),
               "full": k.count("full") + k.count("cross")}
    return {"paged_decode": attends["window"] + attends["full"],
            "paged_decode_window": attends["window"],
            "paged_decode_full": attends["full"],
            "ssm_scan": k.count("mamba"), "ssm_step": k.count("mamba")}[group]


def decode_attention_tokens(spec: Dict[str, Any], context: int) -> Dict[str, int]:
    """Cached tokens that one decoded token's attention reads, by kernel
    group: each window layer the last `sliding_window` of `context`, the
    full layer and every cross layer all of it."""
    return {"paged_decode_window": calls_per_pass(spec, "paged_decode_window")
            * min(context, spec["sliding_window"]),
            "paged_decode": calls_per_pass(spec, "paged_decode_full") * context}


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=12,
              num_attention_heads=8, num_key_value_heads=4, vocab_size=256,
              max_position_embeddings=512, sliding_window=16,
              mamba_d_inner=128, mamba_d_state=4, mamba_dt_rank=4)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    return {**spec, **SHRINK}
