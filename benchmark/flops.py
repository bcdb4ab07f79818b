"""Operations and bytes the algorithms need, computed from shapes. The
yardstick's side of every utilization and roofline share: no PR that claims
a gain can change these.

Conventions: a multiply-add is 2 operations; the embedding lookup is a
gather and counts nothing; a sparse-expert layer counts the experts a token
is routed to (num_experts_per_tok), never all of them; recomputation
(activation checkpointing) counts nothing."""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2  # bytes


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


def roofline_seconds(work: Dict[str, float], peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    compute = work["flops"] / peaks["bf16_flops"]
    memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
