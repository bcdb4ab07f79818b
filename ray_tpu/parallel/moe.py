"""Expert parallelism: MoE routing with all_to_all dispatch over ICI.

Net-new relative to the reference (SURVEY.md §2.4: Ray's MoE story was
"use placement groups to co-locate expert actors"); here experts are a mesh
axis ("ep") and token routing is a compiled ``all_to_all`` — the XLA
collective that is near-free on ICI tori.

Design: Switch/Mixtral-style top-k gating with static capacity (XLA needs
static shapes — capacity-factor dispatch instead of ragged routing),
dispatch/combine as einsums that land on the MXU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec


def top_k_gating(
    router_logits: jax.Array, num_selected: int
) -> Tuple[jax.Array, jax.Array]:
    """router_logits [T, E] → (weights [T, k], expert_ids [T, k]).
    Weights are softmaxed over the selected k (Mixtral convention)."""
    gate_vals, expert_ids = jax.lax.top_k(router_logits, num_selected)
    weights = jax.nn.softmax(gate_vals, axis=-1)
    return weights, expert_ids


def sigmoid_bias_gating(
    router_logits: jax.Array, bias: jax.Array, num_selected: int,
    norm_topk: bool = True, scale: float = 1.0, softmax_all: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """router_logits [T, E] float32, bias [E] -> (weights [T, k],
    expert_ids [T, k]). Scores are sigmoids (`softmax_all`: a softmax over
    all E outputs); the k experts are chosen by score + bias (the bias
    balances load and says nothing of how much an expert matters), and the
    weights are the chosen experts' scores WITHOUT it, over their sum +
    1e-6 if `norm_topk`, times `scale`. Feeds what `top_k_gating` feeds
    (models/transformer.py `_moe_gate`: the dropless form's combine matrix,
    or `_moe_route`'s slot assignment)."""
    score = functools.partial(jax.nn.softmax, axis=-1) if softmax_all \
        else jax.nn.sigmoid
    scores = score(router_logits.astype(jnp.float32))
    _, expert_ids = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                  num_selected)
    weights = jnp.take_along_axis(scores, expert_ids, axis=-1)
    if norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return weights * scale, expert_ids


def _dispatch_mask(
    expert_ids: jax.Array, weights: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Build dispatch/combine tensors with per-expert capacity.

    expert_ids/weights: [T, k] → dispatch [T, E, C] bool, combine [T, E, C].
    Tokens beyond an expert's capacity are dropped (standard capacity-factor
    semantics; the residual stream carries them unchanged).
    """
    T, k = expert_ids.shape
    flat_ids = expert_ids.reshape(-1)  # [T*k] in token-major order
    onehot = jax.nn.one_hot(flat_ids, num_experts, dtype=jnp.int32)  # [T*k, E]
    pos = jnp.cumsum(onehot, axis=0) - 1  # position within expert queue
    my_pos = jnp.sum(pos * onehot, axis=-1)  # [T*k]
    keep = my_pos < capacity
    # [T*k, E, C]
    disp = (
        jax.nn.one_hot(flat_ids, num_experts, dtype=jnp.float32)[:, :, None]
        * jax.nn.one_hot(jnp.where(keep, my_pos, capacity), capacity + 1, dtype=jnp.float32)[:, None, :capacity]
    )
    combine = disp * weights.reshape(-1)[:, None, None]
    disp = disp.reshape(T, k, num_experts, capacity).sum(axis=1)
    combine = combine.reshape(T, k, num_experts, capacity).sum(axis=1)
    return disp, combine


def moe_layer_local(
    x: jax.Array,
    router_w: jax.Array,
    w_in: jax.Array,
    w_gate: jax.Array,
    w_out: jax.Array,
    axis_name: str = "ep",
    num_selected: int = 2,
    capacity_factor: float = 1.25,
    activation=jax.nn.silu,
) -> jax.Array:
    """Per-rank MoE FFN body — call inside shard_map with BOTH tokens and
    experts sharded on ``axis_name`` (token-dispatch design: each rank routes
    its token shard to the expert-owning ranks and gets results back, two
    ``all_to_all``s total).

    x [T_local, D] (tokens split over axis_name); router_w [D, E_global]
    replicated; w_in/w_gate [E_local, D, F]; w_out [E_local, F, D] (experts
    split over axis_name). Returns [T_local, D] (same token sharding).
    """
    n = jax.lax.psum(1, axis_name)
    T, D = x.shape
    E_local = w_in.shape[0]
    E = E_local * n
    capacity = max(1, int(capacity_factor * T * num_selected / E))
    # pad capacity to a friendly multiple for MXU tiling
    capacity = -(-capacity // 4) * 4

    # scope names as models/transformer.py's MoE paths: a profile's
    # readers key on them
    with jax.named_scope("route"):
        logits = x @ router_w  # [T, E]
        weights, expert_ids = top_k_gating(logits, num_selected)
        disp, combine = _dispatch_mask(expert_ids, weights, E, capacity)

    with jax.named_scope("dispatch"):
        expert_inputs = jnp.einsum("td,tec->ecd", x, disp)  # [E, C, D]
        # split expert axis across ranks -> all_to_all over the ep ring
        expert_inputs = expert_inputs.reshape(n, E_local, capacity, D)
        routed = jax.lax.all_to_all(
            expert_inputs, axis_name, split_axis=0, concat_axis=0, tiled=False
        )  # [n, E_local, C, D] — now grouped by *source* rank for MY experts
        routed = routed.reshape(n, E_local, capacity, D)

    # expert FFN (SwiGLU): batched einsum over local experts — MXU-friendly
    with jax.named_scope("experts"):
        h = jnp.einsum("necd,edf->necf", routed, w_in)
        g = jnp.einsum("necd,edf->necf", routed, w_gate)
        y = jnp.einsum("necf,efd->necd", activation(g) * h, w_out)

    # route back and combine
    with jax.named_scope("combine"):
        returned = jax.lax.all_to_all(
            y, axis_name, split_axis=0, concat_axis=0, tiled=False
        ).reshape(E, capacity, D)
        return jnp.einsum("ecd,tec->td", returned, combine)


def aux_load_balance_loss(router_logits: jax.Array, expert_ids: jax.Array, num_experts: int) -> jax.Array:
    """Switch-transformer load-balance auxiliary loss (per shard)."""
    probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E]
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], num_experts, dtype=probs.dtype), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac_tokens * frac_probs)
