"""Decoder-only transformer, TPU-first.

Design (vs the reference, which orchestrates torch models it never owns —
upstream ray has no model code; parity target is the model zoo its Train/
Serve examples run via HF/DeepSpeed/vLLM):

- Parameters are a plain pytree with layers STACKED on a leading axis and
  the forward a `lax.scan` over them — one compiled block regardless of
  depth, which keeps XLA compile times flat at 32+ layers.
- Every parameter carries logical axes (parallel/sharding.py); activations
  are re-annotated inside the jit so GSPMD propagates the mesh layout and
  inserts ICI collectives (DP/FSDP/TP/SP/EP are rules changes, not model
  changes).
- bfloat16 weights/activations on the MXU, float32 for softmax/norm/loss
  accumulations.
- Attention is ops.flash_attention (Pallas on TPU) or parallel.ring
  (sequence-parallel) per config.
- MoE layers use capacity-factor dispatch einsums at the jit level: XLA
  turns the expert-sharded einsums into all_to_alls over the ep axis.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import (
    apply_rope,
    expert_groups,
    expert_step,
    flash_attention,
    gather_rows,
    group_rows,
    grouped_ffn,
    grouped_fits,
    grouped_rows_bound,
    grouped_tile,
    groups_fit,
    groups_rows_bound,
    layer_norm,
    rms_norm,
    rope_frequencies,
)
from ..ops.attention import FLASH_RESIDUAL_NAMES
from ..ops.moe import GROUPED_MIN_ROWS, GROUPED_RESIDUAL_NAMES
from ..parallel.moe import sigmoid_bias_gating, top_k_gating
from ..parallel.sharding import _current_mesh, constrain, per_shard, split_ways
from .config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init + logical axes
# ---------------------------------------------------------------------------


def _one_block_only(cfg: ModelConfig, what: str) -> None:
    if cfg.is_stack:
        raise NotImplementedError(
            f"{what} runs one kind of layer, rotary attention and an FFN; "
            f"{cfg.name!r} is a stack of unlike layers, which models/stack.py "
            "runs: `forward`, the engine's programs and, where every kind is "
            "one of config.TRAINABLE_KINDS, `loss_fn` / `param_axes` / "
            "`make_train_step`; no pipeline stages, no contiguous-cache "
            "generate")


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random-init parameters (f32 master copy; cast at use sites)."""
    if cfg.is_stack:
        from . import stack

        return stack.init_params(cfg, key)
    D, F, L, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.hdim
    k_emb, k_pos, k_head, k_layers = jax.random.split(key, 4)

    def norm_init(shape):
        return jnp.ones(shape, jnp.float32)

    def dense_init(k, shape, scale=0.02):
        return (jax.random.normal(k, shape) * scale).astype(jnp.float32)

    def init_layer(k):
        ks = jax.random.split(k, 8)
        out_scale = 0.02 / (2 * L) ** 0.5
        layer = {
            "ln1": norm_init((D,)),
            "wq": dense_init(ks[0], (D, H, hd)),
            "wk": dense_init(ks[1], (D, KVH, hd)),
            "wv": dense_init(ks[2], (D, KVH, hd)),
            "wo": dense_init(ks[3], (H, hd, D), out_scale),
            "ln2": norm_init((D,)),
        }
        if cfg.norm == "layernorm":
            layer["ln1_b"] = jnp.zeros((D,))
            layer["ln2_b"] = jnp.zeros((D,))
        if cfg.is_moe:
            E = cfg.num_experts
            layer["router"] = dense_init(ks[4], (D, E))
            layer["w_in"] = dense_init(ks[5], (E, D, F))
            layer["w_gate"] = dense_init(ks[6], (E, D, F))
            layer["w_out"] = dense_init(ks[7], (E, F, D), out_scale)
        else:
            layer["w_in"] = dense_init(ks[5], (D, F))
            layer["w_out"] = dense_init(ks[7], (F, D), out_scale)
            if cfg.activation in _GATE_ACT:
                layer["w_gate"] = dense_init(ks[6], (D, F))
            else:
                layer["b_in"] = jnp.zeros((F,))
                layer["b_out"] = jnp.zeros((D,))
        return layer

    params: Params = {
        "embed": dense_init(k_emb, (V, D)),
        "layers": jax.vmap(init_layer)(jax.random.split(k_layers, L)),
        "final_norm": norm_init((D,)),
    }
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((D,))
    if cfg.positional == "learned":
        params["pos_emb"] = dense_init(k_pos, (cfg.max_seq_len, D), 0.01)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (D, V))
    return params


def param_axes(cfg: ModelConfig) -> Params:
    """Logical-axis tree matching init_params' structure exactly.

    The leading "stage" on layer entries is the stacked-layer axis:
    sharded over pp/dcn_pp when the mesh has those axes (params live
    pp-sharded from birth, so the pipelined train step round-trips state
    without resharding); unsharded on every other mesh.
    """
    if cfg.is_stack:
        from . import stack

        return stack.param_axes(cfg)
    layer = {
        "ln1": ("stage", "norm"),
        "wq": ("stage", "embed", "heads", None),
        "wk": ("stage", "embed", "heads", None),
        "wv": ("stage", "embed", "heads", None),
        "wo": ("stage", "heads", None, "embed"),
        "ln2": ("stage", "norm"),
    }
    if cfg.norm == "layernorm":
        layer["ln1_b"] = ("stage", "norm")
        layer["ln2_b"] = ("stage", "norm")
    if cfg.is_moe:
        layer["router"] = ("stage", "embed", None)
        layer["w_in"] = ("stage", "expert", "embed", "expert_mlp")
        layer["w_gate"] = ("stage", "expert", "embed", "expert_mlp")
        layer["w_out"] = ("stage", "expert", "expert_mlp", "embed")
    else:
        layer["w_in"] = ("stage", "embed", "mlp")
        layer["w_out"] = ("stage", "mlp", "embed")
        if cfg.activation in _GATE_ACT:
            layer["w_gate"] = ("stage", "embed", "mlp")
        else:
            layer["b_in"] = ("stage", "mlp")
            layer["b_out"] = ("stage", "norm")
    axes: Params = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("norm",),
    }
    if cfg.norm == "layernorm":
        axes["final_norm_b"] = ("norm",)
    if cfg.positional == "learned":
        axes["pos_emb"] = (None, "embed")
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _norm(x, w, b, cfg):
    if cfg.norm == "layernorm":
        return layer_norm(x, w, b, eps=cfg.norm_eps)
    return per_shard(
        functools.partial(rms_norm, eps=cfg.norm_eps),
        (("batch", "seq", "embed"), ("norm",)), ("batch", "seq", "embed"),
        x, w)


_QKV_AXES = ("batch", None, "heads", None)  # seq gathered: flash sees all keys


def _flash(q, k, v, mesh=None, **kernel):
    return per_shard(
        functools.partial(flash_attention, causal=True, **kernel),
        (_QKV_AXES,) * 3, _QKV_AXES, q, k, v, mesh=mesh)


def _head_norm(x, w, eps):
    """RMSNorm of x [B,T,heads,hd] over the axes its weight has: each
    head's own lanes (w [hd], shared by the heads) or the whole projected
    vector (w [heads,hd]); float32 inside (XLA fuses it into the rotary
    turn)."""
    xf = x.astype(jnp.float32)
    over = tuple(range(-w.ndim, 0))
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=over, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def _qkv(x, lp, cfg, rope_tables, positions, rotary=None):
    """x [B,T,D] -> q [B,T,H,hd], k, v [B,T,KVH,hd], q and k normalised
    where the model says (`cfg.qk_norm`: per head, or over the whole vector
    where the weights are [heads,hd]) and turned to `positions`
    [B,T] (None: 0..T-1) where it is rotary (`rotary`; None: what the model
    says of all its layers). Shared by the training block below and the
    serve path's (models/stack.py)."""
    dtype = x.dtype
    q = jnp.einsum("btd,dhk->bthk", x, lp["wq"].astype(dtype))
    k = jnp.einsum("btd,dhk->bthk", x, lp["wk"].astype(dtype))
    v = jnp.einsum("btd,dhk->bthk", x, lp["wv"].astype(dtype))
    if cfg.qk_norm:
        q = _head_norm(q, lp["q_norm"], cfg.norm_eps)
        k = _head_norm(k, lp["k_norm"], cfg.norm_eps)
    if cfg.positional == "rope" if rotary is None else rotary:
        cos, sin = rope_tables
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    return q, k, v


def _attention(x, lp, cfg, rope_tables, positions, mesh=None):
    dtype = x.dtype
    q, k, v = _qkv(x, lp, cfg, rope_tables, positions)
    q = checkpoint_name(constrain(q, ("batch", "seq", "heads", None)), "attn_q")
    k = checkpoint_name(constrain(k, ("batch", "seq", "heads", None)), "attn_k")
    v = checkpoint_name(constrain(v, ("batch", "seq", "heads", None)), "attn_v")
    if cfg.attn_impl == "ring":
        from ..comm.mesh import get_mesh
        from ..parallel.ring import ring_attention

        # GQA under sp: replicate kv heads (ring kernel is MHA-shaped)
        g = cfg.n_heads // cfg.kv_heads
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        o = ring_attention(q, k, v, mesh if mesh is not None else get_mesh())
    else:
        o = _flash(q, k, v, mesh)
    o = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(dtype))
    return constrain(o, ("batch", "seq", "embed"))


# the gate's activation of a gated second half, dense or an expert's:
# down(act(gate x) * (up x)); `cfg.activation` names it
_GATE_ACT = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}
# the two [.., d_ff] products of a dense gated second half, `gate x` first
_FFN_NAMES = ("ffn_gate", "ffn_up")


def _dense_ffn(x, lp, cfg, named=False):
    """`named`: the two products of a gated half carry `_FFN_NAMES` for a
    checkpoint to save them by (the training layer's; the serve programs
    take none: a name lowers to nothing but moves the numbers in a lowered
    program's private function names)."""
    dtype = x.dtype
    h = jnp.einsum("btd,df->btf", x, lp["w_in"].astype(dtype))
    gated = cfg.activation in _GATE_ACT
    if gated:
        g = jnp.einsum("btd,df->btf", x, lp["w_gate"].astype(dtype))
        if named:
            g = checkpoint_name(g, _FFN_NAMES[0])
            h = checkpoint_name(h, _FFN_NAMES[1])
        h = _GATE_ACT[cfg.activation](g) * h
    else:
        h = jax.nn.gelu(h + lp["b_in"].astype(dtype))
    h = constrain(h, ("batch", "seq", "mlp"))
    out = jnp.einsum("btf,fd->btd", h, lp["w_out"].astype(dtype))
    if not gated:
        out = out + lp["b_out"].astype(dtype)
    return constrain(out, ("batch", "seq", "embed"))


def _shared_experts(x, lp, cfg):
    """The shared experts over the normed rows x [B,T,D] of an expert
    layer: ONE gated FFN of width `cfg.d_ff_shared` (n shared experts of
    width w, each with weight 1, are one of n x w: `sh_in`, `sh_gate`
    [D, n w] are their columns side by side and `sh_out` [n w, D] their
    rows) that every row passes through, whatever form the routed experts
    beside it take: a dense product of its own, one more pass over the
    rows and the weights."""
    with jax.named_scope("shared_experts"):
        return _dense_ffn(x, {"w_in": lp["sh_in"], "w_gate": lp["sh_gate"],
                              "w_out": lp["sh_out"]}, cfg)


def moe_capacity(cfg, T: int) -> int:
    """Slots each expert has for a batch row of T tokens: the capacity
    factor's share, a multiple of 4 for tiling, T * k at the most. Static.
    At `capacity >= T` nothing can be dropped (a token's k experts are
    distinct, so an expert gets at most T of a row's choices), which is
    the rule `_moe_ffn` picks the dropless form by and `moe_rows_computed`
    counts by."""
    E, k = cfg.num_experts, cfg.num_selected_experts
    raw = -int(-cfg.capacity_factor * T * k // E)  # ceil
    return min(max((raw + 3) // 4 * 4, 4), T * k)


def _moe_sharded(mesh) -> bool:
    """Whether a model axis shards tokens, experts or params: such a mesh
    keeps the dense dispatch, whose einsums partition as sharded
    contractions under GSPMD (indices across a sharded seq (sp) or expert
    (ep) axis, or scatter outputs under fsdp/tp layouts, would force
    per-layer allgathers). Pure data-parallel axes only shard the batch."""
    return mesh is not None and any(
        mesh.shape.get(ax, 1) > 1 for ax in ("ep", "sp", "tp", "fsdp"))


def _moe_dropless(cfg, T: int, mesh) -> bool:
    """Whether `_moe_ffn` dispatches nothing for rows of T tokens: no slot
    can overflow, and the mesh is one the row forms run on."""
    return moe_capacity(cfg, T) >= T and not _moe_sharded(mesh)


def moe_grouped(cfg, B: int, T: int, mesh) -> Optional[Tuple[int, int]]:
    """Whether a program over B rows of T tokens that does not know which
    of its rows are live (a training row, the plain forward, `Verify`) runs
    its experts as `_moe_ffn_grouped`, the tokens' choices sorted by expert
    and each held expert over its own rows: where it dispatches nothing,
    a held expert can expect `GROUPED_MIN_ROWS` rows or more (under that
    every expert over every row costs no more than the sort), there are no
    identity experts, and the widths tile and fit the kernels. -> (tile,
    bound): the sorted buffer's tile and its rows (ops/moe.py), or None:
    `_moe_ffn_dropless_ids` runs. The program's static shape and mesh
    decide, as for `_moe_dropless`."""
    k, W = cfg.num_selected_experts, cfg.router_width
    N, D, F = B * T, cfg.d_model, cfg.expert_ff
    if not ("moe" in cfg.second_halves and _moe_dropless(cfg, T, mesh)
            and N * k >= GROUPED_MIN_ROWS * W and not cfg.experts_zero
            and D % 128 == 0 and F % 128 == 0):
        return None
    tile = grouped_tile(N * k / W)
    if not grouped_fits(D, F, jnp.dtype(cfg.dtype).itemsize, tile):
        return None
    return tile, grouped_rows_bound(N, k, cfg.num_experts, W, tile)


def moe_step_visits(cfg, mesh) -> bool:
    """Whether a decode STEP (one token a row, the mode knows which rows
    are live) runs its experts as `moe_ffn_step`, the experts a live row
    chose and no others: wherever the model has experts and the step
    dispatches nothing. The rule is the program's static shape and mesh, as
    `_moe_dropless` is; the serve path's layers (models/stack.py
    `run_stack`) and the engine's counters both ask it."""
    return "moe" in cfg.second_halves and _moe_dropless(cfg, 1, mesh)


def moe_seq_groups(cfg, B: int, T: int, mesh) -> bool:
    """Whether a program of the serve path over B rows of T tokens that
    keeps its keys (a bucket, a prefill chunk: the mode knows which rows
    hold a token) runs its experts as `moe_ffn_groups`, each expert over
    the rows that chose it and no others: wherever the model has experts,
    the program dispatches nothing and its rows lie whole in the kernel's
    fast memory (`ops/moe.py groups_fit`). The rule is the program's static
    shape and mesh, as `moe_step_visits` is; `run_stack` and the engine's
    counters both ask it."""
    return ("moe" in cfg.second_halves and _moe_dropless(cfg, T, mesh)
            and groups_fit(B * T, cfg.d_model, cfg.num_experts,
                           cfg.expert_ff, jnp.dtype(cfg.dtype).itemsize))


def moe_rows_computed(cfg, B: int, T: int, mesh=None, tokens=None) -> int:
    """Expert rows ONE expert layer computes for a program of B rows of T
    tokens, whichever form `_moe_ffn` takes: every expert over the
    program's own B * T tokens when it dispatches nothing, else
    B x experts x capacity padded slots. The engine's
    `serve_moe_rows_computed` counts with it, but for a decode step that
    visits (`moe_step_visits`): its rows are the experts VISITED x B, which
    the device counts and the span's readback brings. `tokens`: how many of
    the rows hold a token, for a bucket or a chunk of the serve path
    (`moe_seq_groups`): its rows are the passes of the experts its tokens
    chose, of which the host knows a BOUND (`ops/moe.py groups_rows_bound`:
    never less than the kernel's passes cover). The ROUTED experts' rows
    alone: the shared experts (`cfg.d_ff_shared`) run over every row of
    every program once, which the engine counts beside these
    (`serve_moe_shared_rows`)."""
    if tokens is not None and moe_seq_groups(cfg, B, T, mesh):
        # a share layer: a token's choices that can fall on a held expert
        k = min(cfg.num_selected_experts, cfg.num_experts)
        return groups_rows_bound(B * T, cfg.num_experts, k, tokens)
    per_row = T if _moe_dropless(cfg, T, mesh) else moe_capacity(cfg, T)
    return cfg.num_experts * B * per_row


def _moe_gate(x, lp, cfg):
    """Router logits in float32 -> gating by the model's rule
    (`cfg.router`: top k softmaxed, or sigmoid scores, or a softmax over
    every output, chosen with a per-expert bias) -> (logits [B,T,W],
    weights [B,T,k], expert_ids [B,T,k]) over all W = `cfg.router_width`
    outputs, held here or not. One implementation for every MoE
    formulation."""
    k = cfg.num_selected_experts
    logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32), lp["router"])
    if cfg.router == "sigmoid":
        weights, expert_ids = sigmoid_bias_gating(
            logits, lp["router_bias"], k, cfg.norm_topk, cfg.routed_scale)
    elif cfg.router == "softmax_all":
        weights, expert_ids = sigmoid_bias_gating(
            logits, lp["router_bias"], k, cfg.norm_topk, cfg.routed_scale,
            softmax_all=True)
    else:
        weights, expert_ids = top_k_gating(logits, k)  # [B,T,k]
    return logits, weights, expert_ids


def _moe_route(x, lp, cfg, gate=None):
    """Shared routing core for BOTH capacity-bound MoE formulations:
    `_moe_gate` (`gate`: its result, where the layer made the choice
    earlier, from another tensor than x) -> cumsum slot assignment under
    capacity. One implementation so the dense and gather paths can never
    diverge on capacity/drop semantics (their numerical-parity contract).

    -> (logits, weights [B,T,k], flat_ids [B,T*k], my_pos, keep, capacity)
    """
    B, T, _ = x.shape
    E, k = cfg.num_experts, cfg.num_selected_experts
    logits, weights, expert_ids = gate or _moe_gate(x, lp, cfg)
    capacity = moe_capacity(cfg, T)
    flat_ids = expert_ids.reshape(B, T * k)
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)  # [B,T*k,E]
    pos_in_expert = jnp.cumsum(onehot, axis=1) - 1
    my_pos = jnp.sum(pos_in_expert * onehot, axis=-1)  # [B,T*k]
    keep = my_pos < capacity
    return logits, weights, expert_ids, flat_ids, my_pos, keep, capacity


def _moe_aux(logits, expert_ids, num_experts):
    """Switch-style load-balance auxiliary loss."""
    probs = jax.nn.softmax(logits, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_ids[..., 0], num_experts, dtype=jnp.float32),
        axis=(0, 1),
    )
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return num_experts * jnp.sum(frac_tokens * frac_probs)


def _moe_dispatch(x, lp, cfg, gate=None):
    """x [B,T,D] -> (dispatch [B,T,E,C] f32, combine [B,T,E,C] f32, aux)."""
    B, T, _ = x.shape
    E, k = cfg.num_experts, cfg.num_selected_experts
    logits, weights, expert_ids, flat_ids, my_pos, keep, capacity = _moe_route(
        x, lp, cfg, gate)
    slot = jnp.where(keep, my_pos, 0)
    # ONE big [B,T*k,E,C] mask build; combine reuses it scaled by the
    # slot weight (the second full one-hot product was ~half the
    # dispatch-construction traffic for identical structure)
    disp = (
        jax.nn.one_hot(flat_ids, E, dtype=jnp.float32)
        * keep[..., None]
    )[..., None] * jax.nn.one_hot(slot, capacity, dtype=jnp.float32)[:, :, None, :]
    combine = disp * weights.reshape(B, T * k)[:, :, None, None]
    combine = combine.reshape(B, T, k, E, capacity).sum(axis=2)
    disp = disp.reshape(B, T, k, E, capacity).sum(axis=2)
    return disp, combine, _moe_aux(logits, expert_ids, E)


def _moe_ffn(x, lp, cfg, gate=None):
    """One algorithm (the same gating, the same weighted sum) in the form
    its static shape and mesh allow: where no slot can overflow (every
    dropless chunk and bucket, `Verify`, a training row) the dispatch is
    pure cost and the experts run over the tokens where they lie; under a
    capacity, rows are gathered to their slots and scattered back; a
    sharded mesh keeps the dense dispatch. `gate`: `_moe_gate`'s result
    where the layer scored another tensor than the one the experts compute
    on, x (None: x is scored, here).

    The serve path's own programs do not come here; models/stack.py
    `_experts` hands them to the two forms that read the experts where
    they lie (ops/moe.py), the same sum with the terms left out that are
    zero. A decode STEP, whose mode knows which rows hold a sequence: a few
    rows touch a few experts and the step's time is the experts' bytes, so
    `moe_ffn_step` visits the experts a live row chose and runs each over
    all rows (`moe_step_visits`). A BUCKET or a prefill CHUNK, whose mode
    knows which rows hold a token: 256 rows touch every expert, and each
    run over all of them multiplies E / k times the rows that chose it, so
    `moe_ffn_groups` runs each expert over its own rows (`moe_seq_groups`).
    Training, `Verify`, the plain forward (which may be differentiated) and
    every program on a sharded mesh keep the forms below. No flag, option
    or model's name decides."""
    mesh = _current_mesh()
    if _moe_dropless(cfg, x.shape[1], mesh):
        return _moe_ffn_dropless(x, lp, cfg, gate)
    if cfg.counts_choices:
        raise ValueError(
            f"{cfg.name!r}: a layer that holds a share of the experts, or "
            "identity experts, has the dropless form alone (slot tables "
            "over the held experts are not written): capacity_factor >= "
            "num_experts / num_selected_experts, and no sharded mesh")
    if _moe_sharded(mesh):
        return _moe_ffn_dense(x, lp, cfg, gate)
    return _moe_ffn_gather(x, lp, cfg, gate)


def _moe_ffn_dropless(x, lp, cfg, gate=None):
    return moe_ffn_ids(x, lp, cfg, gate)[:2]


def moe_ffn_ids(x, lp, cfg, gate=None):
    """The expert layer of a program that dispatches nothing and does not
    know its live rows, in the form `moe_grouped` picks: the choices sorted
    by expert and each held expert over its own rows, or every expert over
    every row. -> (out, aux, expert_ids [B,T,k])."""
    grouped = moe_grouped(cfg, *x.shape[:2], _current_mesh())
    if grouped is None:
        return _moe_ffn_dropless_ids(x, lp, cfg, gate)
    return _moe_ffn_grouped(x, lp, cfg, gate, *grouped)


def _moe_ffn_grouped(x, lp, cfg, gate, tile: int, bound: int):
    """`_moe_ffn_dropless_ids` for rows in their thousands: the same
    gating and the same sum at the same rounding points (each product
    rounded to the activations' type, the chosen experts' results weighted
    and summed in float32, rounded once), over the rows that chose a held
    expert and no others. The tokens' choices that fall on held experts are
    sorted by expert into a buffer of `bound` rows in tiles of `tile`
    (scope `sort`; ops/moe.py `group_rows` gives the row -> token table and
    its inverse), the grouped product runs each expert over its own tiles
    (`experts`; `grouped_ffn`, which has a backward), and the weighted rows
    are summed back at their tokens (`combine`). A row moves by a gather
    both ways and in both passes (`_rows_in`, `_rows_out`): no row is
    scatter-added. Dropless: a routing that needs more than `bound` rows (a
    share layer's alone can: `grouped_rows_bound`) poisons the layer's
    output with NaN, and the train step, which counts every layer's
    choices, raises (train/lm.py). -> (out, aux, expert_ids [B,T,k])."""
    B, T, D = x.shape
    N, E, k = B * T, cfg.num_experts, cfg.num_selected_experts
    with jax.named_scope("route"):
        logits, weights, expert_ids = gate or _moe_gate(x, lp, cfg)
        aux = _moe_aux(logits, expert_ids, cfg.router_width)
        if cfg.hc_streams > 1:
            # a checkpoint keeps the up products IN THE BUFFER'S ORDER, so
            # the backward's sort has to be the forward's row for row. Under
            # one stream the router reads a norm of the kept attention half
            # and chooses again as it chose; under several its input is
            # MIXED again from the layer's input, a last bit of a bfloat16
            # stream differs, a token's last choice flips and every row
            # behind it in that expert's group meets another row's products
            # (chip, PR 58: the experts' gradient 0.8 off). The choice itself
            # is kept: 2 x k numbers a token
            weights = checkpoint_name(weights, MOE_CHOICE_NAMES[0])
            expert_ids = checkpoint_name(expert_ids, MOE_CHOICE_NAMES[1])
    with jax.named_scope("sort"):
        rows = jax.lax.stop_gradient(group_rows(
            expert_ids.reshape(N, k), weights.reshape(N, k),
            cfg.experts_first, E, tile, bound))
        sorted_x = _rows_in(x.reshape(N, D), rows)
    with jax.named_scope("experts"):
        y = grouped_ffn(_GATE_ACT[cfg.activation], tile, sorted_x,
                        lp["w_in"], lp["w_gate"], lp["w_out"],
                        rows["tile_expert"], rows["used"])
    with jax.named_scope("combine"):
        out = _rows_out(y, weights.reshape(N, k), rows)
        out = jnp.where(rows["rows"] > bound, jnp.nan, out)
        # kept by a checkpoint that keeps the up products: a norm after the
        # sublayer reads it in the backward, which then sorts and multiplies
        # for the router's gradient alone and adds nothing up again
        out = checkpoint_name(out, GROUPED_RESIDUAL_NAMES[2])
        return (constrain(out.reshape(B, T, D), ("batch", "seq", "embed")),
                aux, expert_ids)


def _take_rows(x, token):
    """x [N, D] at the sorted buffer's rows. A padding row (token N) reads
    the last token's, which costs no pass to blank it and which nothing
    sums: its weight is 0, no slot names it, and what the experts make of
    it meets a zero cotangent."""
    return jnp.take(x, token, axis=0, mode="clip")


@jax.custom_vjp
def _rows_in(x, rows):
    """x [N, D] -> the sorted buffer [bound, D]: row r is its token's. The
    gradient sums a token's rows through the inverse table (float32,
    rounded once), where XLA's transpose of the gather scatter-adds."""
    return _take_rows(x, rows["token"])


def _rows_in_bwd(rows, d_sorted):
    return gather_rows(d_sorted, rows), None


_rows_in.defvjp(lambda x, rows: (_rows_in(x, rows), rows), _rows_in_bwd)


@jax.custom_vjp
def _rows_out(y, weights, rows):
    """The sorted buffer's results y [bound, D], weights [N, k] float32 ->
    out[n] = sum over j of weights[n, j] * y[slot[n, j]] in float32,
    rounded once to y's type. A row's gradient is a gather too: its token's
    cotangent times the row's weight. A weight's is the product of its row
    with its token's cotangent, read row by row: scalars, each put at its
    own choice (no two rows share one)."""
    return gather_rows(y, rows, weights)


def _rows_out_bwd(res, d_out):
    y, rows = res
    d_rows = _take_rows(d_out, rows["token"]).astype(jnp.float32)
    d_weight = jnp.sum(y.astype(jnp.float32) * d_rows, axis=1)
    d_weights = jnp.zeros((rows["slot"].size,), jnp.float32).at[
        rows["choice"]].set(d_weight, mode="drop", unique_indices=True)
    return ((d_rows * rows["weight"][:, None]).astype(y.dtype),
            d_weights.reshape(rows["slot"].shape), None)


_rows_out.defvjp(lambda y, weights, rows: (_rows_out(y, weights, rows),
                                           (y, rows)), _rows_out_bwd)


def _moe_combine(x, lp, cfg, gate=None):
    """The gating as the forms that dispatch nothing use it -> (c [B,T,E]
    float32: a token's k weights at its experts among the E held ones and
    zero elsewhere; identity [B,T]: the weight its choices put on identity
    experts, None where the model has none; aux; expert_ids [B,T,k] over
    all `cfg.router_width` outputs)."""
    E, W = cfg.num_experts, cfg.router_width
    logits, weights, expert_ids = gate or _moe_gate(x, lp, cfg)
    c = jnp.sum(jax.nn.one_hot(expert_ids, W, dtype=jnp.float32)
                * weights[..., None], axis=2)  # float32, as the scores
    aux = _moe_aux(logits, expert_ids, W)
    identity = None
    if cfg.experts_zero:
        identity = jnp.sum(c[..., cfg.experts_routed:], axis=-1)
    if W != E:
        c = c[..., cfg.experts_first:cfg.experts_first + E]
    return c, identity, aux, expert_ids


def _moe_ffn_dropless_ids(x, lp, cfg, gate=None):
    """The expert layer where `moe_capacity(cfg, T) >= T`, so nothing can
    be dropped: no slot tables, no gather, no scatter. Every expert runs
    over the program's own N = B * T tokens (E * N rows, never more than
    the padded forms' B * E * capacity and 4 x / 2 x fewer in a decode
    step of 32 top 4 / 8 top 2), and a float32 combine matrix c[N, E], a
    token's k weights at its k experts and zero elsewhere, sums them:
    out[n] = sum_e c[n, e] * expert_e(x_n), what the padded forms compute
    too; an expert is the gated FFN `cfg.activation` names (`_GATE_ACT`).
    The expert axis leads ([E, N, F]) so the weights are read as they lie;
    x is shared by the experts and never copied E times. `Verify`, training
    rows and the plain forward take this form; of the serve path's programs
    a decode step, which knows its live rows, takes `moe_ffn_step`, and a
    bucket or a chunk, which knows the rows that hold a token,
    `moe_ffn_groups` (`_moe_ffn`): the same sum at the same rounding points.

    A layer that holds a share of the experts (`cfg.num_experts` of
    `cfg.experts_routed`, from `cfg.experts_first`) routes over all of
    them and keeps the held columns of c: the other columns' part of the
    sum is another chip's, and is left out. A choice that falls on one of
    the `cfg.experts_zero` identity experts adds its weight times x, with
    no product. -> (out, aux, expert_ids [B,T,k])."""
    dtype = x.dtype
    B, T, D = x.shape
    E = cfg.num_experts
    with jax.named_scope("route"):
        c, identity, aux, expert_ids = _moe_combine(x, lp, cfg, gate)
    xs = x.reshape(B * T, D)
    with jax.named_scope("experts"):
        h = jnp.einsum("nd,edf->enf", xs, lp["w_in"].astype(dtype))
        g = jnp.einsum("nd,edf->enf", xs, lp["w_gate"].astype(dtype))
        h = _GATE_ACT[cfg.activation](g) * h
        y = jnp.einsum("enf,efd->end", h, lp["w_out"].astype(dtype))
    with jax.named_scope("combine"):
        out = jnp.sum(y.astype(jnp.float32)
                      * c.reshape(B * T, E).T[:, :, None], axis=0)
        if cfg.experts_zero:
            out = out + identity.reshape(B * T, 1) * xs.astype(jnp.float32)
        out = out.astype(dtype).reshape(B, T, D)
        return constrain(out, ("batch", "seq", "embed")), aux, expert_ids


def moe_ffn_step(x, lp, cfg, gate, live):
    """`_moe_ffn_dropless_ids` for a decode STEP x [B,1,D] whose rows
    `live` (bool [B]) hold a sequence: the same gating, the same float32
    combine and the same rounding points, over the experts that at least
    one live row chose; the others' terms are zero for every live row, and
    their weights are not read (ops/moe.py; a dead slot's row chooses too,
    touches nothing and is discarded by the engine). `lp["experts"]`:
    (the segment's stacks `w_in`, `w_gate` [layers,E,D,F] and `w_out`
    [layers,E,F,D], this layer's index in them): the kernel reads the
    layer where it lies (models/stack.py `run_stack`). Identity experts and
    the held slice of a share layer act on c, as there.
    -> (out, expert_ids [B,1,k], experts visited: int32 [])."""
    dtype = x.dtype
    B, _, D = x.shape
    E, first = cfg.num_experts, cfg.experts_first
    with jax.named_scope("route"):
        c, identity, _, expert_ids = _moe_combine(x, lp, cfg, gate)
        chosen = jax.nn.one_hot(expert_ids, cfg.router_width, dtype=bool)
        hit = jnp.any(chosen & live[:, None, None, None],
                      axis=(0, 1, 2))[first:first + E]
    xs = x.reshape(B, D)
    stacks, layer = lp["experts"]
    with jax.named_scope("experts"):
        out, visited = expert_step(
            xs, c.reshape(B, E), hit, stacks["w_in"], stacks["w_gate"],
            stacks["w_out"], layer, _GATE_ACT[cfg.activation])
    with jax.named_scope("combine"):
        if cfg.experts_zero:
            out = out + identity.reshape(B, 1) * xs.astype(jnp.float32)
        out = out.astype(dtype).reshape(B, 1, D)
        return constrain(out, ("batch", "seq", "embed")), expert_ids, visited


def moe_ffn_groups(x, lp, cfg, gate, held):
    """`_moe_ffn_dropless_ids` for a program of many tokens x [B,T,D] whose
    rows `held` (bool [B,T]) hold a token: the same gating, the same float32
    combine and the same rounding points, each expert over the rows that
    chose it and no others (ops/moe.py `expert_groups`; the terms left out
    are zero there). A row of padding chooses too, joins no group, and its
    output is the identity experts' part alone (nobody reads it).
    `lp["experts"]`, identity experts and the held slice of a share layer:
    as in `moe_ffn_step`. -> (out, expert_ids [B,T,k])."""
    dtype = x.dtype
    B, T, D = x.shape
    E, first = cfg.num_experts, cfg.experts_first
    with jax.named_scope("route"):
        c, identity, _, expert_ids = _moe_combine(x, lp, cfg, gate)
        chosen = jnp.any(
            jax.nn.one_hot(expert_ids, cfg.router_width, dtype=bool), axis=2)
        member = (chosen[..., first:first + E] & held[..., None])
    xs = x.reshape(B * T, D)
    stacks, layer = lp["experts"]
    with jax.named_scope("experts"):
        out = expert_groups(
            xs, c.reshape(B * T, E), member.reshape(B * T, E),
            stacks["w_in"], stacks["w_gate"], stacks["w_out"], layer,
            _GATE_ACT[cfg.activation])
    with jax.named_scope("combine"):
        if cfg.experts_zero:
            out = out + identity.reshape(B * T, 1) * xs.astype(jnp.float32)
        out = out.astype(dtype).reshape(B, T, D)
        return constrain(out, ("batch", "seq", "embed")), expert_ids


def _moe_ffn_dense(x, lp, cfg, gate=None):
    dtype = x.dtype
    with jax.named_scope("route"):
        disp, combine, aux = _moe_dispatch(x, lp, cfg, gate)
    with jax.named_scope("dispatch"):
        expert_in = jnp.einsum("btd,btec->becd", x, disp.astype(dtype))
        expert_in = constrain(expert_in, ("batch", "expert", None, "embed"))
    y = _experts(expert_in, lp, cfg)
    with jax.named_scope("combine"):
        out = jnp.einsum("becd,btec->btd", y, combine.astype(dtype))
        return constrain(out, ("batch", "seq", "embed")), aux


def _experts(expert_in, lp, cfg):
    """The gated FFN (`cfg.activation`) over every expert's rows:
    [B,E,C,D] -> [B,E,C,D]."""
    dtype = expert_in.dtype
    with jax.named_scope("experts"):
        h = jnp.einsum("becd,edf->becf", expert_in, lp["w_in"].astype(dtype))
        g = jnp.einsum("becd,edf->becf", expert_in,
                       lp["w_gate"].astype(dtype))
        h = constrain(_GATE_ACT[cfg.activation](g) * h,
                      ("batch", "expert", None, "expert_mlp"))
        return jnp.einsum("becf,efd->becd", h, lp["w_out"].astype(dtype))


def _moe_ffn_gather(x, lp, cfg, gate=None):
    """Gather/scatter token routing under a capacity (`capacity < T`:
    capacity-factor training on a single chip and non-ep meshes; every
    shape the serve path runs is dropless and dispatches nothing): the
    dense [T,E,C] dispatch/combine einsums cost O(T*E*C*D) MXU flops
    while routing is really just row movement, and this path is O(E*C*D)
    memory traffic instead, over capacity_factor * k * T padded rows
    where the dropless form would compute E * T. Slot tables come from
    the same cumsum-position assignment (identical capacity-drop
    semantics, numerically equal to the dense path, pinned by test
    parity); expert inputs are a row gather, outputs a row scatter-add;
    backward is the mirror pair, all static shapes. No benchmark cell
    runs it (section 7 of PERF.md: `mixtral-8x7b.train-packed` is
    queued); what its gather and scatter-add cost at a capacity equal to
    T is PERF.md section 6, PR 33."""
    dtype = x.dtype
    B, T, D = x.shape
    E = cfg.num_experts
    with jax.named_scope("route"):
        (logits, weights, expert_ids, flat_ids, my_pos, keep,
         capacity) = _moe_route(x, lp, cfg, gate)
        k = cfg.num_selected_experts
        safe = jnp.where(keep, my_pos, capacity)  # overflow slot sliced off
        bi = jnp.arange(B)[:, None]
        tok = jnp.broadcast_to((jnp.arange(T * k) // k)[None, :], (B, T * k))
        # slot tables [B,E,C]: source token, validity, combine weight
        tok_of = jnp.zeros((B, E, capacity + 1), jnp.int32).at[
            bi, flat_ids, safe].set(tok)[:, :, :capacity]
        valid = jnp.zeros((B, E, capacity + 1), jnp.float32).at[
            bi, flat_ids, safe].set(1.0)[:, :, :capacity]
        w_of = jnp.zeros((B, E, capacity + 1), jnp.float32).at[
            bi, flat_ids, safe].set(weights.reshape(B, T * k))[:, :, :capacity]
        aux = _moe_aux(logits, expert_ids, E)
    with jax.named_scope("dispatch"):
        gath = jax.vmap(lambda xb, ib: xb[ib])(
            x, tok_of.reshape(B, E * capacity))
        expert_in = gath.reshape(B, E, capacity, D) \
            * valid[..., None].astype(dtype)
        expert_in = constrain(expert_in, ("batch", "expert", None, "embed"))
    y = _experts(expert_in, lp, cfg)
    with jax.named_scope("combine"):
        yw = y * (w_of * valid)[..., None].astype(dtype)
        out = jax.vmap(lambda ib, yb: jnp.zeros((T, D), dtype).at[ib].add(yb))(
            tok_of.reshape(B, E * capacity), yw.reshape(B, E * capacity, D))
        return constrain(out, ("batch", "seq", "embed")), aux


# what a layer of several residual streams keeps for its backward beside the
# flash kernel's: the raw mixing coefficients of each sublayer ([24] a token
# under 4 streams) and the attention sublayer's output, a row of d_model, from
# which the backward mixes the 4-wide stream again instead of keeping it
HC_COEF_NAME, HC_OUT_NAME = "mhc_coef", "attn_out"
# ... and a token's choice of experts with its weights (`_moe_ffn_grouped`)
MOE_CHOICE_NAMES = ("moe_weights", "moe_choice")


def hc_coefficients(xs, lp, cfg, tag: str):
    """The mixing coefficients of one sublayer's residual path (`cfg.hc_*`;
    manifold-constrained hyper-connections) from the n streams `xs` (each
    [B,T,D]) and the sublayer's own `<tag>_phi` [n,D,n*n+2n], `<tag>_b` and
    `<tag>_a` (a_pre, a_post, a_res) -> (H_pre [n,B,T], H_post [n,B,T],
    H_res [n,n,B,T]), float32. The token's n streams side by side are
    RMS-normalised as ONE vector with no weight (it would fold into phi)
    and projected; H_res is `cfg.hc_sinkhorn_iters` rounds of column then
    row normalisation of exp(clipped), which the gradient flows through.
    The tokens lie on the LAST axis of every coefficient: a [.., n, n]
    matrix a token would fill a 16th of its tiles."""
    n, (B, T, _) = cfg.hc_streams, xs[0].shape
    f32 = jnp.float32
    phi = lp[tag + "_phi"].astype(xs[0].dtype)
    # (v / rms(v)) phi = (v phi) / rms(v): the streams are read as they lie
    u = sum(jnp.einsum("btd,dc->btc", x, phi[j], preferred_element_type=f32)
            for j, x in enumerate(xs))
    ms = sum(jnp.mean(jnp.square(x.astype(f32)), axis=-1) for x in xs) / n
    u = u * jax.lax.rsqrt(ms + cfg.hc_eps)[..., None]
    u = checkpoint_name(u, HC_COEF_NAME)
    u = jnp.moveaxis(u, -1, 0).reshape(-1, B * T)
    a, b = lp[tag + "_a"].astype(f32), lp[tag + "_b"].astype(f32)[:, None]
    pre = jax.nn.sigmoid(a[0] * u[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * u[n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * u[2 * n:] + b[2 * n:], *cfg.hc_res_clamp))
    m = m.reshape(n, n, B * T)  # [row i, column j, token]

    def sinkhorn(m, _):  # one round: the columns, then the rows
        m = m / (jnp.sum(m, axis=0, keepdims=True) + cfg.hc_eps)
        return m / (jnp.sum(m, axis=1, keepdims=True) + cfg.hc_eps), None

    # a loop, not 20 rounds written out: 36 residual paths a step (forward,
    # recomputation, backward) are 1440 rounds of a few small fusions each
    m, _ = jax.lax.scan(sinkhorn, m, None, length=cfg.hc_sinkhorn_iters)
    return (pre.reshape(n, B, T), post.reshape(n, B, T),
            m.reshape(n, n, B, T))


def _residual(x, lp, cfg, tag: str, sublayer, keep: Optional[str] = None):
    """The residual path round ONE sublayer, `sublayer`: h -> (y, what it
    hands on). One stream (x [B,T,D]): x + y. `cfg.hc_streams` n > 1 (x
    [B,n,T,D]): the sublayer reads sum_j H_pre[j] x[j] (scope `mhc_pre`)
    and x+[i] = sum_j H_res[i,j] x[j] + H_post[i] y (scope `mhc_post`),
    float32 inside, the streams stored in x's dtype; y carries the name
    `keep` for a checkpoint to save it by. The streams are taken apart once
    and every sum is a stream's own, so no float32 copy of all n is ever
    whole (nor, in the backward, of their cotangent). -> (x, what the
    sublayer handed on)."""
    n = cfg.hc_streams
    if n == 1:
        y, handed = sublayer(x)
        return x + y, handed
    f32, dtype = jnp.float32, x.dtype
    xs = [x[:, j] for j in range(n)]
    with jax.named_scope("mhc_pre"):
        pre, post, res = hc_coefficients(xs, lp, cfg, tag)
        h = sum(pre[j][..., None] * xs[j].astype(f32) for j in range(n))
    y, handed = sublayer(h.astype(dtype))
    if keep:
        y = checkpoint_name(y, keep)
    with jax.named_scope("mhc_post"):
        return jnp.stack([
            (sum(res[i, j][..., None] * xs[j].astype(f32) for j in range(n))
             + post[i][..., None] * y.astype(f32)).astype(dtype)
            for i in range(n)], axis=1), handed


def _ffn_half(x, lp, cfg, moe=None, experts=None, named=False):
    """A layer's second half, x + FFN(norm(x)) or the experts in its place
    (`moe`; None: what the whole model has; the shared experts beside them
    where the model has those, `cfg.d_ff_shared`), the norm AFTER the sublayer
    where the model says (`cfg.post_norm`: x + norm(FFN(x))) -> (x, aux
    loss); where the model has several residual streams, their mixing in
    the sum's place (`_residual`). `experts`: what runs the experts over the
    normed rows, h -> (y,
    what it hands back in the aux loss's place) (None: `_moe_ffn`): the
    serve path's layers (models/stack.py), which say for each layer which
    half it has, count in their carry there. Shared by them and the
    training block below, which has a dense half's products `named`
    (`_dense_ffn`)."""
    moe = cfg.is_moe if moe is None else moe
    place = cfg.norm_place

    def half(x):
        h = x if place == "post" else _norm(x, lp["ln2"], lp.get("ln2_b"), cfg)
        if moe:
            y, aux = experts(h) if experts else _moe_ffn(h, lp, cfg)
            if cfg.d_ff_shared:  # beside the routed sum, whatever its form
                y = y + _shared_experts(h, lp, cfg)
        else:
            y, aux = _dense_ffn(h, lp, cfg, named), jnp.zeros((), jnp.float32)
        if place == "post":
            y = _norm(y, lp["ln2"], lp.get("ln2_b"), cfg)
        elif place == "both":
            y = _norm(y, lp["ln2_post"], None, cfg)
        if cfg.residual_multiplier != 1.0:
            y = y * cfg.residual_multiplier
        return y, aux

    with jax.named_scope("moe" if moe else "ffn"):
        return _residual(x, lp, cfg, "hc2", half)


def _block(x, lp, cfg, rope_tables, positions, mesh=None):
    # The training layer: its own loop (run_layers: remat, sharding
    # constraints, ring attention) over the projections and the second
    # half that the serve path's "attn" layer (models/stack.py) runs too.
    # Scope names are what a profile's readers key on, here as there.
    with jax.named_scope("attn"):
        h = _norm(x, lp["ln1"], lp.get("ln1_b"), cfg)
        x = checkpoint_name(
            x + _attention(h, lp, cfg, rope_tables, positions, mesh), "attn_half")
    return _ffn_half(x, lp, cfg, named=True)


# What a layer ALWAYS keeps for its backward under `cfg.remat`: the
# attention half (the flash kernel's output and log-sum-exp, the turned q,
# k, v it read, and the residual stream after the o projection), so the
# backward recomputes the two norms and runs no attention kernel or
# projection a second time. Per layer and row of T tokens that is
# T x (2 x d_model + (H + 2 x KVH) x head) activations + 4 x H x T bytes of
# lse. Of the second half, a dense gated FFN's `gate` and `up` products
# ([.., d_ff], the larger half) are kept as far as the device has room
# (`kept_under_remat`); the experts' are recomputed. `remat=False` keeps
# everything.
_KEPT_UNDER_REMAT = FLASH_RESIDUAL_NAMES + ("attn_q", "attn_k", "attn_v", "attn_half")
# the share of the device's limit that the rule's estimate leaves free:
# the batches an input pipeline holds ahead, what the allocator cannot hand
# out of a memory in pieces, and whatever the estimate does not know of
_REMAT_MARGIN = 0.10


def _kept_widths(cfg) -> Dict[str, int]:
    """name -> the bytes a layer keeps under it for ONE position (a row's
    token) of the traced batch."""
    act = jnp.dtype(cfg.dtype).itemsize
    H, KVH, hd = cfg.n_heads, cfg.kv_heads, cfg.hdim
    flash_out, flash_lse = FLASH_RESIDUAL_NAMES
    return {flash_out: H * hd * act, flash_lse: 4 * H,
            "attn_q": H * hd * act, "attn_k": KVH * hd * act,
            "attn_v": KVH * hd * act, "attn_half": cfg.d_model * act,
            **{name: cfg.d_ff * act for name in _FFN_NAMES}}


def kept_under_remat(cfg, *, layers: int, positions: int, param_itemsize: int,
                     memory: Optional[Tuple[int, int]]):
    """The names a checkpointed layer saves, from what a trace can observe:
    the model's shapes, the `layers` the loop scans, the `positions` (rows x
    tokens) of the traced batch that ONE device holds, the bytes of a
    parameter as traced, and the device's `memory` (its limit, what is in
    use on it now; None: the backend keeps no count, as the CPU and a
    compile for a described chip). -> (names, held): the attention half,
    then `gate`, then `up` of a dense gated FFN, each one stack of
    layers x positions x d_ff, as many as fit beside `held` with
    `_REMAT_MARGIN` of the limit left over. `held` estimates what the step
    holds without them: what is on the device now (the train state; the
    parameters at the least), a gradient the size of the parameters, the
    stacks of the attention half and of the loop's carry (the layer's
    input, which every checkpoint keeps), and the larger of two things that
    are not live together: the float32 logits with their gradient, and a
    layer's working set in the backward (five values of [positions, d_ff]
    and its weights' gradient). It leans high: against the chip's
    compiler's buffer assignment it read +0.3 GB at 1 x 8192 of 8 layers of
    Mistral-7B's widths (12.62 for 12.30, and 14.50 for 14.18 with `gate`;
    the chip peaked at 12.20 and 14.08), +0.15 GB at 4 x 2048 of `llama-2b`
    and +4 GB at 2 x 2048 of a vocabulary of 128256, whose logits the
    compiler schedules away from the gradient (PR 47). Pure: the same
    arguments, the same names."""
    if memory is None or cfg.is_moe or cfg.activation not in _GATE_ACT:
        return _KEPT_UNDER_REMAT, None
    limit, in_use = memory
    widths = _kept_widths(cfg)
    wide, carry = widths[_FFN_NAMES[0]], widths["attn_half"]
    params = cfg.param_count() * param_itemsize
    held = (max(in_use, params) + params
            + layers * positions * (sum(widths[n] for n in _KEPT_UNDER_REMAT)
                                    + carry)
            + max(2 * positions * cfg.vocab_size * 4,
                  5 * positions * wide + params // cfg.n_layers))
    room = (1 - _REMAT_MARGIN) * limit - held
    fit = min(len(_FFN_NAMES), max(0, int(room // (layers * positions * wide))))
    return _KEPT_UNDER_REMAT + _FFN_NAMES[:fit], held


def _kept_now(cfg, layer_params, x) -> Tuple[str, ...]:
    """`kept_under_remat` for the loop being traced over `layer_params`
    ([L', ...] leaves) from the carry x [B,T,D], with the device's memory
    as it reads at this moment; the decision goes out as a gauge of the
    kept bytes by name and one log line."""
    from ..util import profiler

    mesh = _current_mesh()
    layers = layer_params["wq"].shape[0]
    positions = -(-x.shape[0] * x.shape[1] // split_ways(("batch", "seq"), mesh))
    memory = profiler.device_memory(
        mesh.local_devices if mesh is not None else jax.local_devices()[:1])
    names, held = kept_under_remat(
        cfg, layers=layers, positions=positions,
        param_itemsize=layer_params["wq"].dtype.itemsize, memory=memory)
    kept_bytes = {name: layers * positions * width * (name in names)
                  for name, width in _kept_widths(cfg).items()}
    profiler.publish_remat_kept(kept_bytes)
    if held is not None:
        from ..core.logging import get_logger

        get_logger("models.transformer").info(
            "remat keeps %s: %.3f GB a device in %d layers x %d positions; "
            "without the FFN's the step holds an estimated %.3f GB (%.3f in "
            "use now) of a limit of %.3f GB, of which %.0f%% stays free",
            ", ".join(names), sum(kept_bytes.values()) / 1e9, layers,
            positions, held / 1e9, memory[1] / 1e9, memory[0] / 1e9,
            100 * _REMAT_MARGIN)
    return names


def _remat(body, cfg, kept=None):
    """The layer loop's body as `cfg.remat` wants it differentiated: a
    checkpoint that saves the names `kept` (None: the attention half)."""
    if not cfg.remat:
        return body
    kept = _KEPT_UNDER_REMAT if kept is None else kept
    return jax.checkpoint(
        body, policy=jax.checkpoint_policies.save_only_these_names(*kept))


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def _embed_lookup(table: jax.Array, tokens: jax.Array, dtype, mesh=None) -> jax.Array:
    """Embedding lookup, mesh-aware.

    When the active mesh shards the table (tp on vocab / fsdp on embed),
    a plain gather forces GSPMD into an "involuntary full
    rematerialization" — the table-propagated sharding on the gather
    output cannot be resharded to the batch-sharded activation layout
    efficiently. The one-hot matmul form partitions cleanly (it is just a
    dot, which GSPMD knows how to shard on both operands), keeps the
    lookup on the MXU, and makes the backward a matmul instead of a
    scatter-add. On unsharded meshes the gather is cheaper — keep it."""
    if mesh is None:
        mesh = _current_mesh()  # callers outside a mesh context pass theirs
    # vocab->tp, embed->fsdp are the only rules that shard the table
    table_sharded = mesh is not None and any(
        mesh.shape.get(a, 1) > 1 for a in ("tp", "fsdp")
    )
    if not table_sharded:
        return table[tokens].astype(dtype)
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=dtype)
    return jnp.einsum("btv,vd->btd", onehot, table.astype(dtype))


def _prologue(params, tokens, cfg, positions=None, mesh=None):
    """Shared embed + positional prologue -> (x [B,T,D], rope_tables)."""
    dtype = jnp.dtype(cfg.dtype)
    T = tokens.shape[1]
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, dtype, mesh=mesh)
        rope_tables = None
        if cfg.positional == "learned":
            pos = positions if positions is not None else jnp.arange(T)[None, :]
            x = x + params["pos_emb"][pos].astype(dtype)
        elif cfg.positional == "rope":
            rope_tables = rope_frequencies(
                cfg.hdim, cfg.max_seq_len, cfg.rope_theta)
        return constrain(x, ("batch", "seq", "embed")), rope_tables


def _lm_head(x, params, cfg) -> jax.Array:
    """Shared final-norm + head epilogue -> logits [B,T,V] f32."""
    with jax.named_scope("lm_head"):
        x = _norm(x, params["final_norm"], params.get("final_norm_b"), cfg)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32),
                            head.astype(jnp.float32))
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        if cfg.logits_softcap:
            logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
        return constrain(logits, ("batch", "seq", "vocab"))


def _head_logits(x, pick, params, cfg, einsum: str):
    """The serve path's head: final norm of x [B,T,D], then the head in f32
    (+ softcap) on the rows `pick` keeps alone."""
    x = _norm(x, params["final_norm"], params.get("final_norm_b"), cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if cfg.tie_embeddings and head.dtype != jnp.float32:
        # the tied table as it is stored, accumulated in f32: a float32
        # copy of a 200k-row table is 2 GB a step
        logits = jnp.einsum(einsum, pick(x).astype(head.dtype), head,
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum(einsum, pick(x).astype(jnp.float32),
                            head.astype(jnp.float32))
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits


def run_layers(
    layer_params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    rope_tables,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Scan a stacked layer slice: ({leaf: [L', ...]}, x) -> (x, aux_sum).

    The slice need not be the full depth — pipeline stages (and interleaved
    virtual chunks, which own several non-contiguous slices) scan whatever
    leading-axis window of the stacked layer leaves they were assigned; the
    math is position-independent because rope tables / positions come in
    from the caller. One compiled scan regardless of slice length.
    """
    _one_block_only(cfg, "run_layers")

    def body(carry, lp):
        y, aux = _block(carry, lp, cfg, rope_tables, positions)
        return y, aux

    kept = _kept_now(cfg, layer_params, x) if cfg.remat else None
    x, aux = jax.lax.scan(_remat(body, cfg, kept), x, layer_params)
    return x, jnp.sum(aux)


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    positions: Optional[jax.Array] = None,
    route_counts: bool = False,
    mtp_tokens: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """tokens [B, T] -> (logits [B, T, V] f32, aux_loss scalar).
    `route_counts` (a stack that holds experts): a third result, how many
    choices fell on each expert in each expert layer (models/stack.py).
    `mtp_tokens` (a stack with a prediction block): each position's next
    token; the last result is then that block's logits."""
    if cfg.is_stack:
        from . import stack

        return stack.forward(params, tokens, cfg, route_counts, mtp_tokens)
    x, rope_tables = _prologue(params, tokens, cfg, positions)
    x, aux = run_layers(params["layers"], x, cfg, rope_tables, positions)
    return _lm_head(x, params, cfg), aux


def forward_pp(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    mesh,
    num_microbatches: int,
    axis_name: str = "pp",
) -> Tuple[jax.Array, jax.Array]:
    """Pipeline-parallel forward: embed + head replicated compute on every
    pp rank; the layer stack GPipe-pipelined over the `pp` mesh axis
    (parallel/pipeline.py — microbatches flow stage-to-stage by ppermute
    inside one lax.scan). Mathematically identical to forward():
    microbatching only reorders the schedule, so pp losses match dp-only
    losses on the same seed (the dryrun asserts it).

    Reference status per SURVEY §2.4: upstream has no native PP (deferred
    to DeepSpeed); here it is a first-class primitive on the flagship
    model. MoE composes: each stage runs its layers' experts locally
    (gather routing — experts replicated per stage rank on dp x pp
    meshes) and the load-balance aux loss threads through the pipeline
    (pipeline_apply with_aux), so pp MoE losses match dp MoE losses."""
    from ..parallel.pipeline import pipelined
    from ..parallel.sharding import no_constrain

    for ax in ("fsdp", "sp", "ep"):
        # the shard_map in_specs here are dp/pp only: an fsdp/sp/ep axis
        # would silently all-gather ZeRO- or expert-sharded params into
        # every stage rank (HBM blowup) and replicate compute — refuse
        assert mesh.shape.get(ax, 1) == 1, (
            f"forward_pp does not compose with the {ax!r} mesh axis yet; "
            "use dp x pp meshes"
        )
    S = mesh.shape[axis_name]
    L = cfg.n_layers
    assert L % S == 0, f"{L} layers not divisible by {S} pipeline stages"
    x, rope_tables = _prologue(params, tokens, cfg, mesh=mesh)

    def stage_fn(lp_stage, h):
        # per-shard body: constrain() must be inert here (manual axes)
        with no_constrain():
            def body(carry, lp):
                y, aux = _block(carry, lp, cfg, rope_tables, None)
                return y, aux

            # the attention half alone: how many microbatches' stacks the
            # schedule holds at once is not `kept_under_remat`'s to see
            h, aux = jax.lax.scan(_remat(body, cfg), h, lp_stage)
            if cfg.is_moe:
                return h, jnp.sum(aux)  # this stage's layers, this microbatch
            return h

    # [L, ...] stacked layers -> [S, L/S, ...]: contiguous blocks per
    # stage, so the existing over-leading-axis pp sharding maps 1:1
    stage_params = jax.tree.map(
        lambda p: p.reshape(S, L // S, *p.shape[1:]), params["layers"]
    )
    from jax.sharding import PartitionSpec

    data_spec = PartitionSpec("dp") if "dp" in mesh.axis_names else PartitionSpec()
    run = pipelined(stage_fn, mesh, num_microbatches, axis_name=axis_name,
                    data_spec=data_spec, with_aux=cfg.is_moe)
    if cfg.is_moe:
        x, aux = run(stage_params, x)
    else:
        x, aux = run(stage_params, x), jnp.zeros((), jnp.float32)
    return _lm_head(x, params, cfg), aux


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
    z_loss_coef: float = 1e-4,
    forward_fn=None,
    route_counts: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: tokens [B,T], targets [B,T], optional mask [B,T].

    forward_fn overrides the forward (e.g. a pipeline-parallel
    functools.partial(forward_pp, mesh=..., num_microbatches=...)).
    `route_counts`: -> (loss, (metrics, counts)), `forward`'s third result
    beside the metrics (the train step's, for the router's bias). A model
    with a multi-token prediction block (`cfg.mtp_depth`) adds
    `cfg.mtp_weight` x that block's loss (`mtp_loss`, reported beside
    `ce_loss`, which stays the main head's mean)."""
    fwd = forward_fn if forward_fn is not None else forward
    more = {"route_counts": True} if route_counts else {}
    if cfg.mtp_depth:  # the block embeds each position's next token
        more["mtp_tokens"] = batch["targets"]
    logits, aux, *rest = fwd(params, batch["tokens"], cfg, **more)
    total, metrics = loss_from_logits(
        logits, batch["targets"], batch.get("mask"), cfg, aux,
        z_loss_coef=z_loss_coef,
    )
    if cfg.mtp_depth:
        with jax.named_scope("mtp"):
            mtp = mtp_loss(rest.pop(), batch["targets"], batch.get("mask"))
        total = total + cfg.mtp_weight * mtp
        metrics.update(loss=total, mtp_loss=mtp)
    return total, ((metrics, *rest) if route_counts else metrics)


def mtp_loss(logits: jax.Array, targets: jax.Array,
             mask: Optional[jax.Array]) -> jax.Array:
    """The prediction block's cross-entropy: its logits [B,T,V] at position
    i score the token after next, `targets[i + 1]`; the last position has
    none and is masked. The mean over the positions that have one."""
    after_next = jnp.roll(targets, -1, axis=1)
    has = jnp.ones_like(targets, jnp.float32).at[:, -1].set(0.0)
    if mask is not None:  # a masked next position has no target either
        has = has * jnp.roll(mask, -1, axis=1)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, after_next[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - picked) * has) / jnp.maximum(has.sum(), 1.0)


def loss_from_logits(
    logits: jax.Array,
    targets: jax.Array,
    mask: Optional[jax.Array],
    cfg: ModelConfig,
    aux: jax.Array,
    z_loss_coef: float = 1e-4,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The loss epilogue given logits [B,T,V] — shared by loss_fn and the
    MPMD pipeline's last stage (which computes logits from streamed
    activations rather than a full forward)."""
    if mask is None:
        mask = jnp.ones_like(targets, jnp.float32)
    with jax.named_scope("loss"):
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        true_logit = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        nll = (lse - true_logit) * mask
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = nll.sum() / denom
        z_loss = z_loss_coef * jnp.sum(jnp.square(lse) * mask) / denom
        total = ce + z_loss + cfg.router_aux_coef * aux
        acc = jnp.sum((jnp.argmax(logits, -1) == targets) * mask) / denom
    return total, {
        "loss": total,
        "ce_loss": ce,
        "aux_loss": aux,
        "z_loss": z_loss,
        "accuracy": acc,
        "tokens": mask.sum(),
    }


# ---------------------------------------------------------------------------
# KV-cache decode (simple contiguous cache; the serving engine uses the
# paged cache in serve/engine.py instead)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    shape = (cfg.n_layers, batch, max_len, cfg.kv_heads, cfg.hdim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _decode_attention(q, k_cache, v_cache, lengths, cfg):
    """q [B,1,H,hd]; k/v_cache [B,S,KVH,hd]; lengths [B] = #valid keys."""
    B, S, KVH, hd = k_cache.shape
    g = cfg.n_heads // KVH
    qf = q[:, 0].reshape(B, KVH, g, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
    s = s * (hd**-0.5)
    mask = jnp.arange(S)[None, :] < lengths[:, None]  # [B,S]
    s = jnp.where(mask[:, None, None, :], s, -2e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, 1, cfg.n_heads, hd).astype(q.dtype)


def decode_step(
    params: Params,
    cfg: ModelConfig,
    cache,
    tokens: jax.Array,
    positions: jax.Array,
):
    """One token per sequence. tokens [B], positions [B] (0-based index of
    this token). Returns (logits [B,V] f32, new_cache)."""
    _one_block_only(cfg, "decode_step")
    dtype = jnp.dtype(cfg.dtype)
    B = tokens.shape[0]
    x = _embed_lookup(params["embed"], tokens[:, None], dtype)  # [B,1,D]
    if cfg.positional == "learned":
        x = x + params["pos_emb"][positions][:, None].astype(dtype)
        rope_tables = None
    else:
        rope_tables = rope_frequencies(cfg.hdim, cfg.max_seq_len, cfg.rope_theta)
    pos2d = positions[:, None]

    def body(carry, xs):
        x = carry
        lp, k_cache, v_cache = xs
        h = _norm(x, lp["ln1"], lp.get("ln1_b"), cfg)
        q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dtype))
        k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(dtype))
        v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(dtype))
        if cfg.positional == "rope":
            cos, sin = rope_tables
            q = apply_rope(q, cos, sin, pos2d)
            k = apply_rope(k, cos, sin, pos2d)
        k_cache = k_cache.at[jnp.arange(B), positions].set(k[:, 0])
        v_cache = v_cache.at[jnp.arange(B), positions].set(v[:, 0])
        o = _decode_attention(q, k_cache, v_cache, positions + 1, cfg)
        o = jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(dtype))
        x = x + o
        h = _norm(x, lp["ln2"], lp.get("ln2_b"), cfg)
        if cfg.is_moe:
            y, _ = _moe_ffn(h, lp, cfg)
        else:
            y = _dense_ffn(h, lp, cfg)
        return x + y, (k_cache, v_cache)

    x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = _norm(x, params["final_norm"], params.get("final_norm_b"), cfg)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("btd,dv->btv", x.astype(jnp.float32), head.astype(jnp.float32))
    if cfg.logits_softcap:
        logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits[:, 0], {"k": new_k, "v": new_v}


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    max_len: int,
    last_index: Optional[jax.Array] = None,
):
    """Run the full prompt, build a contiguous KV cache of size max_len.

    tokens [B, T]. last_index [B] (default T-1) selects the position whose
    logits are returned — pass true_len-1 when prompts are right-padded to
    a compile bucket. Returns (last_logits [B,V], cache dict).
    """
    _one_block_only(cfg, "prefill")
    dtype = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    with jax.named_scope("embed"):
        x = _embed_lookup(params["embed"], tokens, dtype)
        if cfg.positional == "learned":
            x = x + params["pos_emb"][jnp.arange(T)][None].astype(dtype)
            rope_tables = None
        else:
            rope_tables = rope_frequencies(
                cfg.hdim, cfg.max_seq_len, cfg.rope_theta)

    def body(carry, lp):
        x = carry
        with jax.named_scope("attn"):
            h = _norm(x, lp["ln1"], lp.get("ln1_b"), cfg)
            q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dtype))
            k = jnp.einsum("btd,dhk->bthk", h, lp["wk"].astype(dtype))
            v = jnp.einsum("btd,dhk->bthk", h, lp["wv"].astype(dtype))
            if cfg.positional == "rope":
                cos, sin = rope_tables
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            o = _flash(q, k, v)
            x = x + jnp.einsum("bthk,hkd->btd", o, lp["wo"].astype(dtype))
        with jax.named_scope("moe" if cfg.is_moe else "ffn"):
            h = _norm(x, lp["ln2"], lp.get("ln2_b"), cfg)
            if cfg.is_moe:
                y, _ = _moe_ffn(h, lp, cfg)
            else:
                y = _dense_ffn(h, lp, cfg)
        kpad = jnp.zeros((B, max_len, *k.shape[2:]), dtype).at[:, :T].set(k)
        vpad = jnp.zeros((B, max_len, *v.shape[2:]), dtype).at[:, :T].set(v)
        return x + y, (kpad, vpad)

    x, (kc, vc) = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        x = _norm(x, params["final_norm"], params.get("final_norm_b"), cfg)
        if last_index is None:
            x_last = x[:, -1]
        else:
            x_last = jnp.take_along_axis(
                x, last_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bd,dv->bv", x_last.astype(jnp.float32),
                            head.astype(jnp.float32))
        if cfg.logits_softcap:
            logits = cfg.logits_softcap * jnp.tanh(logits / cfg.logits_softcap)
    return logits, {"k": kc, "v": vc}
