"""Operations and bytes the algorithms need, computed from shapes. The
yardstick's side of every utilization and roofline share: no PR that claims
a gain can change these. The counts of an architecture are its family's
(benchmark/families/<family>.py: `work`, `calls_per_pass`,
`train_flops_per_token`); here are the conventions they keep and the roofline they feed.

Conventions: a multiply-add is 2 operations; the embedding lookup is a
gather and counts nothing; a sparse-expert layer counts the experts a token
is routed to (num_experts_per_tok), never all of them; recomputation
(activation checkpointing) counts nothing."""

from __future__ import annotations

from typing import Any, Dict

BF16 = 2  # bytes


def roofline_seconds(work: Dict[str, float], peaks: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take, and which bound sets it."""
    compute = work["flops"] / peaks["bf16_flops"]
    memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute, memory),
            "bound": "compute" if compute >= memory else "memory"}
