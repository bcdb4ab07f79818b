"""The family of `mistral-7b` and `mixtral-8x7b`: a stack of identical
layers, each `x + Attention(RMSNorm(x))` then `+ FFN(RMSNorm(.))`, rotary
GQA, SwiGLU dense or top-k sparse, untied head. Its plain reference is
benchmark/reference/model.py.

A family file is how the benchmark reaches a model: a configuration's file
names one by its path (`"family": "benchmark/families/mistral.py"`, as it
names its `"reference"`), `common.family(spec)` loads that file, and no
driver, check, tool or metric reader builds a `ModelConfig`, makes a
parameter tree, names a reference or reads an architecture key of `spec`
(`head_dim`, `num_hidden_layers`, `intermediate_size`, `num_*heads`,
`num_local_experts`) except through it. `vocab_size` and
`max_position_embeddings`, which traffic and engine sizes need, stay plain
keys of every configuration. A new family is a new file here, and holds
(`common.FAMILY_HOLDS`; a file that lacks one is refused when it is loaded):

- `model_config(spec, **overrides)`: the configuration's keys to the
  program's `ModelConfig`;
- `init_weights(spec, key)`: traceable; the program's parameter tree (its
  layout is its interface), every leaf bf16, made by the benchmark and not
  by the program's `init_params`;
- the plain reference, which imports nothing of the program: `PAD_TO` (a
  sequence is right-padded to a multiple of it), `logits_at(params, tokens,
  at, spec, mode=None)` and `modes`, the control precisions that `mode` can
  round to;
- the operations and bytes the algorithms need, from shapes, under the
  conventions at the head of benchmark/flops.py: `work`, one `{flops,
  bytes}` function of ONE call for each kernel group of
  benchmark/trace_names* whose roofline a reader takes, and
  `calls_per_pass(spec, group)`, how often one forward pass of the model
  (one decode step) makes that call;
- `tiny(spec)`: the configuration with every size shrunk for the CPU.
  Tests only; no cell of the benchmark may use it;
- where a train cell uses the family (`common.FAMILY_HOLDS_TO_TRAIN`,
  asked for when such a cell is loaded): `nll_and_norm_grads(params,
  tokens, targets, spec, mode=None)` of the reference, the program's side
  of that comparison, `program_probe(cfg, params, tokens, targets)`, and
  `train_flops_per_token(spec, seq)`.

What else this file holds (`matmul_params`, `active_matmul_params`, the
`flash_*` and `paged_decode` functions by name) is its own, and its tests'.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import model as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
nll_and_norm_grads = ref.nll_and_norm_grads
modes = ("int8", "fp8", "kv-int8", "kv-fp8")

# -- the program's side ------------------------------------------------------

STD = 0.02


def model_config(spec: Dict[str, Any], **overrides: Any):
    """HF-style keys of benchmark/configs/<name>.json -> the program's
    ModelConfig. Dropless routing is capacity_factor = experts / selected."""
    from ray_tpu.models import ModelConfig

    experts = int(spec.get("num_local_experts", 0))
    selected = int(spec.get("num_experts_per_tok", 2))
    fields = dict(
        name=spec["model_type"],
        vocab_size=spec["vocab_size"],
        d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"],
        d_ff=spec["intermediate_size"],
        max_seq_len=spec["max_position_embeddings"],
        norm="rmsnorm", activation="swiglu", positional="rope",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        num_experts=experts,
        num_selected_experts=selected,
        capacity_factor=(experts / selected) if experts else 1.25,
        router_aux_coef=float(spec.get("router_aux_loss_coef", 0.0)),
        dtype=spec["torch_dtype"],
    )
    fields.update(overrides)
    return ModelConfig(**fields)


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface), every
    leaf bf16. Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    D, F = spec["hidden_size"], spec["intermediate_size"]
    L, V = spec["num_hidden_layers"], spec["vocab_size"]
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    E = int(spec.get("num_local_experts", 0))
    out_std = STD / (2 * L) ** 0.5
    bf16 = jnp.bfloat16

    def dense(k, shape, std=STD):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(bf16)

    def layer(k):
        ks = jax.random.split(k, 8)
        out = {
            "ln1": jnp.ones((D,), bf16), "ln2": jnp.ones((D,), bf16),
            "wq": dense(ks[0], (D, H, hd)), "wk": dense(ks[1], (D, KVH, hd)),
            "wv": dense(ks[2], (D, KVH, hd)),
            "wo": dense(ks[3], (H, hd, D), out_std),
        }
        if E:
            out.update(router=dense(ks[4], (D, E)),
                       w_in=dense(ks[5], (E, D, F)),
                       w_gate=dense(ks[6], (E, D, F)),
                       w_out=dense(ks[7], (E, F, D), out_std))
        else:
            out.update(w_in=dense(ks[5], (D, F)), w_gate=dense(ks[6], (D, F)),
                       w_out=dense(ks[7], (F, D), out_std))
        return out

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    # one layer at a time: the f32 draws of a whole stacked expert tensor
    # would be a 5.6 GB temporary
    return {"embed": dense(k_emb, (V, D)),
            "layers": jax.lax.map(layer, jax.random.split(k_layers, L)),
            "final_norm": jnp.ones((D,), bf16),
            "lm_head": dense(k_head, (D, V))}


def program_probe(cfg, params, tokens, targets):
    """The program's own forward and backward (models.forward, the function
    the train step differentiates: flash kernels, remat, bf16) on one row:
    per-position negative log-likelihood [T], and the gradient of its mean
    with respect to every layer's first norm weight [L, D]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import forward

    def probe(params, tokens, targets):
        def mean_nll(ln1):
            p = {**params, "layers": {**params["layers"], "ln1": ln1}}
            logits, _ = forward(p, tokens[None], cfg)
            lse = jax.scipy.special.logsumexp(logits[0], axis=-1)
            picked = jnp.take_along_axis(logits[0], targets[:, None], -1)[:, 0]
            nll = lse - picked
            return jnp.mean(nll), nll

        ln1 = params["layers"]["ln1"].astype(jnp.float32)
        (_, nll), g = jax.value_and_grad(mean_nll, has_aux=True)(ln1)
        return nll, g

    return jax.jit(probe)(params, tokens, targets)


# -- operations and bytes, from shapes ---------------------------------------


def matmul_params(spec: Dict[str, Any]) -> Dict[str, int]:
    """Weights that a token multiplies, per layer and in the head."""
    D, F = spec["hidden_size"], spec["intermediate_size"]
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    E = int(spec.get("num_local_experts", 0))
    k = int(spec.get("num_experts_per_tok", 0))
    attn = 2 * D * H * hd + 2 * D * KVH * hd          # q, o, k, v
    ffn = (k * 3 * D * F + D * E) if E else 3 * D * F  # + router
    return {"attn": attn, "ffn": ffn, "head": D * spec["vocab_size"],
            "layers": spec["num_hidden_layers"]}


def active_matmul_params(spec: Dict[str, Any]) -> int:
    p = matmul_params(spec)
    return p["layers"] * (p["attn"] + p["ffn"]) + p["head"]


def attention_forward_flops(spec: Dict[str, Any], seq: int) -> float:
    """Causal self-attention of one sequence of `seq` tokens, all layers:
    QK^T and PV, each 2 * H * hd operations per (query, key) pair, over
    seq * (seq + 1) / 2 pairs."""
    H, hd = spec["num_attention_heads"], spec["head_dim"]
    pairs = seq * (seq + 1) / 2
    return spec["num_hidden_layers"] * 2 * 2 * H * hd * pairs


def train_flops_per_token(spec: Dict[str, Any], seq: int) -> float:
    """Forward + backward (2x the forward) of one token in rows of `seq`."""
    forward = 2 * active_matmul_params(spec) \
        + attention_forward_flops(spec, seq) / seq
    return 3 * forward


def flash_forward(spec: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """One call of the causal flash-attention forward kernel (one layer)."""
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    flops = batch * 2 * 2 * H * hd * seq * (seq + 1) / 2
    # q and o at H heads, k and v at KVH heads, each read or written once
    bytes_ = batch * seq * hd * (2 * H + 2 * KVH) * BF16
    return {"flops": flops, "bytes": bytes_}


def flash_backward(spec: Dict[str, Any], batch: int, seq: int) -> Dict[str, float]:
    """The backward of the same call (all its kernels together): five
    products per (query, key) pair where the forward has two (S recomputed
    once, dV, dP, dQ, dK). Reads q, k, v, o, do; writes dq, dk, dv."""
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    flops = batch * 5 * 2 * H * hd * seq * (seq + 1) / 2
    bytes_ = batch * seq * hd * (4 * H + 4 * KVH) * BF16
    return {"flops": flops, "bytes": bytes_}


def paged_decode(spec: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """One call of the paged decode-attention kernel (one layer, one step)
    whose sequences hold `context_tokens` cached tokens together."""
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    return {"flops": 2 * 2 * H * hd * context_tokens,
            "bytes": 2 * KVH * hd * BF16 * context_tokens}


work = {"flash_fwd": flash_forward, "flash_bwd": flash_backward,
        "paged_decode": paged_decode}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Every layer attends, and holds a cache of its own."""
    return spec["num_hidden_layers"]


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
              vocab_size=256, max_position_embeddings=512)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    return {**spec, **SHRINK}
