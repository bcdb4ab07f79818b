"""The layers of every model the serving engine runs, as a stack of layer
kinds (`cfg.layer_kinds`): every layer is `x + Mix(norm(x))` and then its
second half, `x + FFN(norm(x))` or the experts (`cfg.second_halves`: a
stack may lead with dense layers); `cfg.norm_place` says where the norms
stand: "post" (the older `cfg.post_norm`): they follow their sublayers,
`x + norm(Mix(x))` and `x + norm(FFN(x))`; "both": a sandwich,
`x + norm'(Mix(norm(x)))` and `x + norm'(FFN(norm(x)))`, four norms a
layer. Mix is one of

  attn    the one-block models' (a plain ModelConfig: every layer): rotary
          or learned-position GQA over the layer's own pages, queries and
          keys normalised per head where `cfg.qk_norm`, the heads' outputs
          times sigmoid(h W_g) lane by lane where `cfg.attn_gate`; trained
  conv    gated short convolution: [B ; C ; x] = h W_in, a causal
          depthwise convolution of B * x over `conv_taps` positions, gated
          by C, then W_out; its tail (the last taps - 1 rows of B * x) per
          sequence
  mamba   selective state space (Mamba-1): conv tail and scan state per
          sequence; hands its scan output on to the gmu layers after it
  window  attention over the last `window` keys, which a decode slot
          holds in a ring of pages of its own, whatever its sequence holds
  swa     rotary GQA over the last `window` keys (`cfg.rope_theta`; the
          "attn" layers beside it follow `cfg.positional`, and here encode
          no position), each layer its OWN keys, which a sequence holds in
          pages of a second page space that the engine's allocator serves:
          its ring is a table of the pages it was given, as many as its
          tokens need and window / page_size + a chunk's pages at the most;
          trained (whole sequences go through the flash kernels' window)
  full    attention over every key; its keys and values are THE cache that
          the cross layers after it read
  gmu     gated memory unit: gates the last mamba layer's scan output
  cross   queries only, over the last full layer's keys and values
  gdn     gated delta rule: q, k, v through a short convolution and SiLU,
          q and k L2-normalised, a [key, value] state matrix per head and
          sequence decayed and corrected a token at a time (ops/gdn.py),
          the output RMS-normalised per head and gated; conv tail and
          state matrix per sequence. The decay is one number a head
          ([a ; b] = h W_ab) or, where `cfg.gdn_channel_rank`, a vector
          over the key channels through a low-rank pair (`dt_bias` a
          lane, beta a projection of its own); the gate silu(h W_gate)
          or, where `cfg.gdn_gate_rank`, the sigmoid of a low-rank pair
          with a bias
  ssd     scalar-decay state space (Mamba-2): [z ; xBC] = h W_in and
          dt = h W_dt, a causal convolution with bias and SiLU over x, B
          and C side by side, per head ONE decay exp(dt A) a token and a
          [state, head] matrix a sequence (ops/ssd.py: the chunked dual
          form for a sequence, one step for the live slots), the output
          plus D x gated by silu(z) and THEN RMS-normalised over a group's
          lanes; conv tail and state matrix per sequence
  mla2    ONE layer that is two blocks and a shortcut: for i in (0, 1):
          x += MLA_i(norm(x)); b = norm(x); if i == 0: s = Experts(b);
          x += FFN_i(b); and at the end x += s, so the experts' product
          passes while the second block runs and the second attention
          never sees it. MLA is latent attention: queries through a normed
          bottleneck, keys and values up-projected from ONE normed latent a
          token beside ONE rotary key the heads share (interleaved pairs);
          the latent and that key are the token's row in the pool, and
          there is no pool of values (ops/mla_attention.py). A sequence
          with no past attends in the plain form (every token's keys and
          values up-projected once, heads of 192 against values of 128
          through the flash kernel); a chunk over cached rows and a decode
          step in the absorbed form (the query carried into the latent's
          space, nothing up-projected per cached token). The experts are a
          share layer (models/transformer.py `_moe_ffn_dropless_ids`)
  mla     that latent attention as ONE mixer a layer, then the layer's own
          second half as for every other kind (a leading dense FFN, or the
          experts beside the shared experts, `cfg.d_ff_shared`); where
          `cfg.q_lora_rank` is 0 the queries are one projection, with no
          bottleneck and no norm. Its rows are the pool's, one a layer.
          The kernels run two shapes: 64 query rows a sequence (109
          operations a byte of row) under `mla2` as published, 32 (54)
          under `mla`

Each mixer is written ONCE, over a small state interface (a *mode*), and
`forward`, the engine's bucket prefill, its chunk program, its decode
program and the speculative programs all run `run_stack` with their own
mode:

  Seq(...)     whole sequences [B, T]: no cache (forward), keys and state
               kept for the engine (bucket prefill), or one chunk of one
               sequence from carried state with its keys in pages
  Decode(...)  one token for every slot: state per slot, window keys in a
               ring of pages per slot, the other keys in the pool; a slot
               whose page table starts at page 0 (the engine's trash page)
               is empty, and its delta-rule state is not touched
  Verify(...)  Decode for S tokens a slot (speculation: pages only)

What a mode reads and writes travels in `carry`, a dict threaded through
the layer scans, so pools and state arrays are updated in place; it holds
what the stack at hand needs and no more (pages alone for the one-block
models). Where `cfg.router_input` is "layer" the router scores the layer's
input stream, before the first norm, and the choice is made before the mixer
runs (scope `route`, then the mixer's, then `moe`). Layers run as
`cfg.segments()`: whole periods scanned, one-off
layers once; the one-block models' stacked parameter dict is their one
segment as it is.

The experts take the form their program's static shape allows (`_experts`).
A STEP (`Decode`, one token a row: the mode knows which rows hold a
sequence) visits the experts that at least one live row chose and reads no
byte of the others (ops/moe.py, models/transformer.py `moe_ffn_step`): a
step's time is its weights' bytes, and a few live rows choose a few
experts. Its kernel reads a layer's experts where they lie, so `run_stack`
hands such a program a segment's `w_in` / `w_gate` / `w_out` stacks whole,
beside the layer's index, and not as the layer scan's slices. A BUCKET or
a prefill CHUNK (a `Seq` that keeps its keys: the mode knows which rows hold
a token) touches every expert, and runs each over the rows that chose it
and no others (`moe_ffn_groups`, the same stacks handed whole): E / k times
fewer rows than every expert over every row. `Verify`, `forward` and a
sharded mesh run every expert over their tokens (`_moe_ffn`), as they did;
no flag, option or model's name decides.

Differential attention (window / full / cross) rides on the plain kernels:
a KV pair is stored as one row [k1 ; k2] (and [v1 ; v2]) of twice the head
size, and a query head is padded with zeros on the side of the other
softmax, so `[q1 ; 0]` scores against k1 alone and `[0 ; q2]` against k2,
and one pass over the pages feeds both softmaxes. The pools and rings are
the page pool of ops/paged_attention.py (a token's KV heads or pairs side
by side in one row), whose ops take the plain queries and keys of this
file: the layout is theirs. The "attn" layers of a plain ModelConfig shard
by the one-block models' rules (a `tp` mesh reaches the paged calls through
the mode) and train through models/transformer.py's own loop over the same
projections and second half. A StackConfig whose kinds are all "attn",
"swa" or "mla" (`config.TRAINABLE_KINDS`; dense, expert and shared second
halves) trains through THIS file: `forward` is what `loss_fn`
differentiates, the plain `Seq` names what the layer loop's checkpoint keeps
(`cfg.remat`: the attention half, the grouped experts' two up products and
their combined result; a latent layer's three bottlenecks), counts every
expert layer's choices for the train step (`route_counts`, `move_router_bias`)
and `param_axes` gives its leaves their logical axes. The other kinds' ops
have no backward yet, and `param_axes` / `make_train_step` refuse them by
name.

The residual is ONE function round both sublayers of a layer
(models/transformer.py `_residual`): `x + y` for one stream, and for
`cfg.hc_streams` n > 1 the mixing of n streams [B,n,T,D] (manifold-
constrained hyper-connections: scopes `mhc_pre`, `mhc_post`; `_Mode.embed`
copies the embedding into every stream, `_run` sums them before the final
norm), where a checkpoint keeps each sublayer's raw coefficients and the
attention sublayer's output and never the n-wide stream. Where
`cfg.mtp_depth`, `forward(.., mtp_tokens=)` runs one more expert layer over
the trunk's stream joined with the next token's embedding (`_mtp`, scope
`mtp`) and hands `loss_fn` a second set of logits through the shared head.
The latent kinds' rotary lanes follow `cfg.rope_yarn` where it is set.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import (
    latent_attention_chunk,
    latent_attention_decode,
    paged_attention_chunk,
    paged_attention_decode,
    paged_attention_verify,
    pool_shape,
    write_latent_then_attend,
    write_then_attend,
)
from ..ops.gdn import gdn_chunk, gdn_step, state_shape
from ..ops.rope import rope_frequencies, yarn_inv_freq, yarn_mscale
from ..ops.ssd import ssd_chunk, ssd_step, state_shape as ssd_state_shape
from ..ops.ssm import ssm_scan, ssm_step
from ..parallel.sharding import _current_mesh, split_ways
from .config import ModelConfig
from .transformer import (
    HC_COEF_NAME,
    HC_OUT_NAME,
    MOE_CHOICE_NAMES,
    _KEPT_UNDER_REMAT,
    _dense_ffn,
    _kept_widths,
    _ffn_half,
    _flash,
    _lm_head,
    _moe_ffn,
    _moe_gate,
    _embed_lookup,
    _norm,
    _prologue,
    _qkv,
    _remat,
    _residual,
    moe_ffn_groups,
    moe_ffn_ids,
    moe_ffn_step,
    moe_grouped,
    moe_seq_groups,
    moe_step_visits,
)
from ..ops.attention import FLASH_RESIDUAL_NAMES
from ..ops.moe import GROUPED_RESIDUAL_NAMES

Params = Dict[str, Any]
_F32 = jnp.float32
# kinds that own rows of state arrays or pools, counted as layers go by
_COUNTED = ("attn", "conv", "mamba", "window", "full", "gdn", "mla2", "swa",
            "ssd", "mla")
# an expert layer's leaves that a step reads where they lie (`run_stack`)
_EXPERT_LEAVES = ("w_in", "w_gate", "w_out")
# what a period of layers keeps for its backward under `cfg.remat`: the
# attention half (models/transformer.py), the two up products of the
# grouped experts (ops/moe.py `grouped_ffn`) and their combined result; the
# norms, the head gate, the router, the sort, the experts' down product (the
# router's gradient reads it), the shared expert and a dense FFN are computed
# again. A latent layer keeps its three bottlenecks (the queries', the
# latent, the shared rotary key: a few hundred lanes a token) and projects
# the heads' q, k and v up from them again; a layer of several residual
# streams keeps each sublayer's raw mixing coefficients and the attention
# sublayer's output in the 4-wide stream's place (`attn_half` is then
# nowhere), and mixes the streams again from the layer's input; its expert
# layers keep the tokens' choices, so that the backward sorts as the forward
_LATENT_NAMES = ("mla_cq", "mla_c", "mla_kr")
_STACK_NAMES = _LATENT_NAMES + (HC_COEF_NAME, HC_OUT_NAME) + MOE_CHOICE_NAMES
KEPT_UNDER_REMAT = _KEPT_UNDER_REMAT + GROUPED_RESIDUAL_NAMES + _STACK_NAMES


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def layer_shapes(cfg: ModelConfig, kind: str,
                 half: str = "ffn") -> Dict[str, tuple]:
    """name -> (shape, init) of one layer of `kind` whose second half is
    `half` ("ffn" or "moe"); init is "w" (normal), "out" (normal, scaled
    down with depth), "one", "zero", or the residual path's "hc_b" / "hc_a"
    (`hc_start`)."""
    D, F, H, KVH, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.kv_heads, cfg.hdim
    Di, N, R, K = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank, cfg.ssm_conv
    out = {"ln1": ((D,), "one"), "ln2": ((D,), "one")}
    if cfg.norm_place == "both":  # a norm after each sublayer too
        out.update(ln1_post=((D,), "one"), ln2_post=((D,), "one"))
    if cfg.norm == "layernorm":
        out.update(ln1_b=((D,), "zero"), ln2_b=((D,), "zero"))
    if cfg.hc_streams > 1:
        # the residual path round each sublayer (`hc1`: the mixer's, `hc2`:
        # the second half's): phi a stream at a time, the bias leaning H_res
        # to the identity, the three scalars small (a_pre, a_post, a_res)
        n = cfg.hc_streams
        for tag in ("hc1", "hc2"):
            out.update({f"{tag}_phi": ((n, D, n * n + 2 * n), "w"),
                        f"{tag}_b": ((n * n + 2 * n,), "hc_b"),
                        f"{tag}_a": ((3,), "hc_a")})
    if half == "moe":
        # the router is as wide as the experts there are; the weights are
        # the held experts'
        E, W, Fe = cfg.num_experts, cfg.router_width, cfg.expert_ff
        out.update(router=((D, W), "w"), w_in=((E, D, Fe), "w"),
                   w_gate=((E, D, Fe), "w"), w_out=((E, Fe, D), "out"))
        if cfg.router != "softmax":
            out.update(router_bias=((W,), "zero"))
        if cfg.d_ff_shared:  # ONE gated FFN every token passes through
            Fs = cfg.d_ff_shared
            out.update(sh_in=((D, Fs), "w"), sh_gate=((D, Fs), "w"),
                       sh_out=((Fs, D), "out"))
    else:
        out.update(w_in=((D, F), "w"), w_gate=((D, F), "w"),
                   w_out=((F, D), "out"))
    if kind in ("mla", "mla2"):
        # one latent attention's leaves: the latent's and the rotary key's
        # down-projections, and the keys' and values' up-projections, are
        # leaves of their own (each is consumed whole where the layer scan
        # hands it over; a slice of a joint leaf was a copy of the weights
        # every step, chip, PR 39); the queries through a bottleneck and
        # its norm, or in one projection where `q_lora_rank` is 0
        ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
        N, R, V = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        latent = dict(wkv_a=((D, kl), "w"), wkr=((D, R), "w"),
                      kv_ln=((kl,), "one"), wk_b=((kl, H, N), "w"),
                      wv_b=((kl, H, V), "w"), wo=((H, V, D), "out"))
        if ql:
            latent.update(wq_a=((D, ql), "w"), q_ln=((ql,), "one"),
                          wq_b=((ql, H, N + R), "w"))
        else:
            latent.update(wq=((D, H, N + R), "w"))
    if kind == "mla2":
        # the two blocks' leaves are named for the block (`wq_a0`,
        # `wq_a1`), for the reason above; the norms are each block's own
        # (before its attention, before its FFN)
        del out["ln1"], out["ln2"]
        block = dict(a_ln=((D,), "one"), p_ln=((D,), "one"), **latent,
                     f_in=((D, F), "w"), f_gate=((D, F), "w"),
                     f_out=((F, D), "out"))
        out.update({f"{n}{i}": v for i in (0, 1) for n, v in block.items()})
    elif kind == "mla":
        out.update(latent)
    elif kind in ("attn", "swa"):
        out.update(wq=((D, H, hd), "w"), wk=((D, KVH, hd), "w"),
                   wv=((D, KVH, hd), "w"), wo=((H, hd, D), "out"))
        if cfg.qk_norm_whole:
            out.update(q_norm=((H, hd), "one"), k_norm=((KVH, hd), "one"))
        elif cfg.qk_norm:
            out.update(q_norm=((hd,), "one"), k_norm=((hd,), "one"))
        if cfg.attn_gate:
            out.update(wg=((D, H, hd), "w"))
    elif kind == "gdn":
        _, Hg, dk, dv = cfg.gdn_dims
        out.update(d_in=((D, Hg * (2 * dk + dv)), "w"),
                   d_conv=((cfg.conv_taps, Hg * (2 * dk + dv)), "w"),
                   d_A_log=((Hg,), "zero"), d_norm=((dv,), "one"),
                   d_out=((Hg * dv, D), "out"))
        if cfg.gdn_channel_rank:  # a decay a key channel, and b alone
            r = cfg.gdn_channel_rank
            out.update(d_fa=((D, r), "w"), d_fb=((r, Hg * dk), "w"),
                       d_b=((D, Hg), "w"), d_dt_b=((Hg * dk,), "zero"))
        else:
            out.update(d_ab=((D, 2 * Hg), "w"), d_dt_b=((Hg,), "zero"))
        if cfg.gdn_gate_rank:
            r = cfg.gdn_gate_rank
            out.update(d_ga=((D, r), "w"), d_gb=((r, Hg * dv), "w"),
                       d_gb_b=((Hg * dv,), "zero"))
        else:
            out.update(d_gate=((D, Hg * dv), "w"))
    elif kind == "conv":
        out.update(c_in=((D, 3 * D), "w"), c_conv=((cfg.conv_taps, D), "w"),
                   c_out=((D, D), "out"))
    elif kind == "ssd":
        # the published in-projection as two leaves: z and x, B, C (the
        # convolution's channels) in one, dt in another whose product
        # stays float32
        _, Hs, _, _, G = cfg.ssd_dims
        conv = Di + 2 * G * N
        out.update(s_in=((D, Di + conv), "w"), s_dt=((D, Hs), "w"),
                   s_conv=((K, conv), "w"), s_conv_b=((conv,), "zero"),
                   s_dt_b=((Hs,), "zero"), s_A_log=((Hs,), "zero"),
                   s_D=((Hs,), "one"), s_norm=((Di,), "one"),
                   s_out=((Di, D), "out"))
    elif kind == "mamba":
        out.update(m_in=((D, 2 * Di), "w"), m_conv=((K, Di), "w"),
                   m_conv_b=((Di,), "zero"), m_x=((Di, R + 2 * N), "w"),
                   m_dt=((R, Di), "w"), m_dt_b=((Di,), "zero"),
                   m_A_log=((N, Di), "zero"), m_D=((Di,), "one"),
                   m_out=((Di, D), "out"))
    elif kind == "gmu":
        out.update(g_in=((D, Di), "w"), g_out=((Di, D), "out"))
    else:
        out.update(wq=((D, H, hd), "w"), bq=((H, hd), "zero"),
                   wo=((H, hd, D), "out"), bo=((D,), "zero"),
                   sub_w=((2 * hd,), "one"),
                   **{n: ((hd,), "w") for n in
                      ("lam_q1", "lam_k1", "lam_q2", "lam_k2")})
        if kind != "cross":
            out.update(wk=((D, KVH, hd), "w"), bk=((KVH, hd), "zero"),
                       wv=((D, KVH, hd), "w"), bv=((KVH, hd), "zero"))
    return out


def hc_start(cfg: ModelConfig, init: str, lean: float = 4.0) -> jax.Array:
    """Where a residual path starts: "hc_a": the three scalars at 0.01 (the
    dynamic term small); "hc_b": H_pre and H_post even (sigmoid(0): every
    stream read at 1/2, the sublayer's output written at 1) and H_res
    leaning to the identity by `lean` in the exponent."""
    n = cfg.hc_streams
    if init == "hc_a":
        return jnp.full((3,), 0.01, _F32)
    return jnp.concatenate([jnp.zeros((2 * n,), _F32),
                            (lean * jnp.eye(n, dtype=_F32)).reshape(-1)])


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random float32 parameters. `layers` is a list of segments
    (cfg.segments()), each a tuple with one dict per layer of the period,
    every leaf stacked over the segment's repeats."""
    out_scale = 0.02 / (2 * cfg.n_layers) ** 0.5

    def leaf(k, shape, init):
        if init in ("w", "out"):
            scale = 0.02 if init == "w" else out_scale
            return jax.random.normal(k, shape, _F32) * scale
        if init in ("hc_b", "hc_a"):
            return hc_start(cfg, init)
        return jnp.full(shape, 1.0 if init == "one" else 0.0, _F32)

    def layer(k, kind, half):
        shapes = layer_shapes(cfg, kind, half)
        ks = jax.random.split(k, len(shapes))
        return {n: leaf(ks[i], *shapes[n]) for i, n in enumerate(sorted(shapes))}

    k_emb, k_layers = jax.random.split(key)
    segments = []
    for first, kinds, repeats in cfg.segments():
        ks = jax.random.split(jax.random.fold_in(k_layers, first),
                              repeats * len(kinds)).reshape(
                                  repeats, len(kinds), -1)
        segments.append(tuple(
            jax.vmap(lambda k, kind=kind, half=cfg.second_halves[first + i]:
                     layer(k, kind, half))(ks[:, i])
            for i, kind in enumerate(kinds)))
    out = {"embed": jax.random.normal(k_emb, (cfg.vocab_size, cfg.d_model),
                                      _F32) * 0.02,
           "layers": segments,
           "final_norm": jnp.ones((cfg.d_model,), _F32)}
    if not cfg.tie_embeddings:
        out["lm_head"] = jax.random.normal(
            jax.random.fold_in(k_emb, 1), (cfg.d_model, cfg.vocab_size),
            _F32) * 0.02
    if cfg.norm == "layernorm":
        out["final_norm_b"] = jnp.zeros((cfg.d_model,), _F32)
    if cfg.mtp_depth:
        k_mtp, k_proj = jax.random.split(jax.random.fold_in(k_layers, cfg.n_layers))
        shapes = mtp_shapes(cfg)
        out["mtp"] = {
            "layer": layer(k_mtp, cfg.layer_kinds[-1], "moe"),
            **{n: leaf(k_proj, *shapes[n]) for n in sorted(shapes)}}
    return out


def mtp_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    """name -> (shape, init) of the prediction block's own leaves beside its
    `layer` (one layer of the stack's last kind with an expert half,
    unstacked): the norms of the two halves it joins, their projection
    [2D, D] and its final norm."""
    D = cfg.d_model
    return {"h_norm": ((D,), "one"), "e_norm": ((D,), "one"),
            "proj": ((2 * D, D), "w"), "final_norm": ((D,), "one")}


# ---------------------------------------------------------------------------
# state: what the engine holds per slot, and what a prefill hands it
# ---------------------------------------------------------------------------


def ring_pages(cfg: ModelConfig, page_size: int, chunk: int = 0) -> int:
    """Pages a sequence holds in each window layer at the most: `window`
    keys span at most window / page_size + 1 pages. Where a prefill chunk
    of `chunk` tokens writes its keys to the ring before it attends (the
    "swa" kind), the ring holds the chunk's pages beside the window's, so
    that the chunk's last keys do not take the place of keys its first
    rows still see: `chunk` is the rows of the WIDEST chunk program the
    engine will run (serve/engine.py `_wide_chunk`), however many of them
    one call of the attention kernel takes."""
    if cfg.window % page_size:
        raise ValueError(f"window {cfg.window} must be a multiple of the "
                         f"page size {page_size}")
    return cfg.window // page_size + max(chunk // page_size, 1)


def new_request_state(cfg: ModelConfig, batch: int, dtype) -> Params:
    """State a prefill hands over beside keys and values: conv tails
    [M,B,K-1,Di] where there are mamba, conv or gdn layers
    (`cfg.conv_tail`), scan state [M,B,N,Di] (float32) where there are
    mamba layers, the delta-rule state matrices [G,B,dk,H*dv] (float32;
    ops/gdn.py lays them out) where there are gdn layers, the state-space
    state matrices [S,B,N,H*P] (float32; ops/ssd.py lays them out) where
    there are ssd layers, and the last
    `window` keys and values of every window layer [W,B,window,KVH,D]
    where there are those. Zeros are a sequence's start; the one-block
    models have none (the empty tree). Where a token's choice of experts
    can fall outside the held ones, `choices` [2] counts those of the
    request's prefill that fell on identity experts and on held ones: no
    state of the model's, it rides here to reach the host with the logits."""
    M, NW = cfg.count("mamba"), cfg.count("window")
    out = {}
    if cfg.counts_choices:
        out.update(choices=jnp.zeros((2,), _F32))
    if cfg.conv_tail[0]:
        layers, rows, width = cfg.conv_tail
        out.update(conv=jnp.zeros((layers, batch, rows, width), dtype))
    if M:
        out.update(
            ssm=jnp.zeros((M, batch, cfg.ssm_state, cfg.ssm_inner), _F32))
    if cfg.gdn_dims[0]:
        layers, heads, dk, dv = cfg.gdn_dims
        out.update(
            gdn=jnp.zeros(state_shape(layers, batch, heads, dk, dv), _F32))
    if cfg.ssd_dims[0]:
        layers, heads, head_dim, d_state, _ = cfg.ssd_dims
        out.update(ssd=jnp.zeros(
            ssd_state_shape(layers, batch, heads, head_dim, d_state), _F32))
    if NW:
        kv = (NW, batch, cfg.window, cfg.pool_heads, cfg.pool_dim)
        out.update(wk=jnp.zeros(kv, dtype), wv=jnp.zeros(kv, dtype))
    return out


def new_engine_state(cfg: ModelConfig, batch: int, page_size: int,
                     act_dtype, cache_dtype, window_pages: int = 0) -> Params:
    """What the engine holds for `batch` decode slots beside the page pool:
    conv tails and scan state per slot, and for the window layers a page
    pool: of 1 + batch * ring pages in which slot b owns pages
    1 + b * ring .. (page 0 is never read), or, where the window layers'
    pages are allocated (`cfg.window_paged`), of `window_pages` pages that
    the engine's allocator hands out (page 0 is its trash page)."""
    st = new_request_state(cfg, batch, act_dtype)
    st.pop("choices", None)  # a span counts its own (the engine's program)
    if cfg.window_paged:
        pool = pool_shape(cfg.window_cache_dims[0], window_pages, page_size,
                          *cfg.window_cache_dims[1:])
        st.update(wk=jnp.zeros(pool, cache_dtype),
                  wv=jnp.zeros(pool, cache_dtype))
    elif "wk" in st:
        pool = pool_shape(cfg.count("window"),
                          1 + batch * ring_pages(cfg, page_size), page_size,
                          cfg.pool_heads, cfg.pool_dim)
        st.update(wk=jnp.zeros(pool, cache_dtype),
                  wv=jnp.zeros(pool, cache_dtype))
    return st


def install_state(state: Params, rs: Params, slot, length,
                  cfg: ModelConfig, page_size: int) -> Params:
    """A prefilled sequence of `length` tokens takes decode slot `slot`:
    its conv tails and its scan, delta-rule and state-space state overwrite
    the slot's (whatever the last occupant left), and its last `window` keys
    go to the slot's ring, each at the place its position has there."""
    out = dict(state)
    for name in ("conv", "ssm", "gdn", "ssd"):
        if name in state:
            out[name] = jax.lax.dynamic_update_slice_in_dim(
                state[name], rs[name].astype(state[name].dtype), slot, 1)
    if "wk" not in state:
        return out
    ring = ring_pages(cfg, page_size)
    span = ring * page_size
    r = jnp.arange(span)
    pos = r + span * jnp.floor_divide(length - 1 - r, span)
    at = jnp.clip(pos - (length - cfg.window), 0, cfg.window - 1)

    def image(tail):  # [W,1,window,KVH,D] -> the ring's pages
        img = tail[:, 0][:, at]
        return img.reshape(pool_shape(img.shape[0], ring, page_size,
                                      cfg.pool_heads, cfg.pool_dim))

    for name in ("wk", "wv"):
        out[name] = jax.lax.dynamic_update_slice_in_dim(
            state[name], image(rs[name]).astype(state[name].dtype),
            1 + slot * ring, 2)
    return out


# ---------------------------------------------------------------------------
# modes: the state interface
# ---------------------------------------------------------------------------


def _dense_attend(q, k, v, scale, window=None):
    """q [B,T,H,D], k/v [B,T,KVH,D], causal (and windowed): the flash
    kernels, whose forward and backward visit the key blocks a window
    reaches and no others (ops/attention.py)."""
    T = q.shape[1]
    # under the kernel's smallest automatic block the sequence is one
    # block (left to itself flash_attention takes its XLA path there)
    block = T if T < 128 else None
    bound = {} if window is None or T <= window else {"window": window}
    return _flash(q, k, v, scale=scale, block_q=block, block_k=block, **bound)


def _expand(x, cfg):
    """x [B,T,D] -> the residual streams [B,n,T,D], each a copy (one
    stream: x as it is). Stream-major, so the last two axes tile as a
    [T,D] activation's do."""
    if cfg.hc_streams == 1:
        return x
    return jnp.broadcast_to(x[:, None], (x.shape[0], cfg.hc_streams,
                                         *x.shape[1:]))


def _collapse(x, cfg):
    """The residual streams summed back into one, [B,T,D]."""
    if cfg.hc_streams == 1:
        return x
    return jnp.sum(x.astype(_F32), axis=1).astype(x.dtype)


class _Mode:
    """What the modes share: where the tokens of x [B,T] are."""

    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        """tokens [B,T] -> x [B,T,D] ([B,n,T,D] under n residual streams:
        the embedding in every one); the mode keeps the tokens' positions
        `at` ([B,T]; None: 0..T-1) and the rotary tables for its attn
        layers."""
        self.at = self.positions(tokens.shape[1])
        x, self.rope = _prologue(params, tokens, self.cfg, self.at, self.mesh)
        if self.cfg.embedding_multiplier != 1.0:
            x = x * self.cfg.embedding_multiplier
        if self.cfg.window_paged:  # rotary whatever the "attn" layers are
            self.rope = rope_frequencies(
                self.cfg.hdim, self.cfg.max_seq_len, self.cfg.rope_theta)
        return _expand(x, self.cfg)

    plain = False  # only the whole-sequence forward is (`Seq.plain`)

    def live_rows(self, T):
        """bool [B]: which rows of a STEP (T = 1) hold a sequence, where
        the mode knows; None for every other program."""
        return None

    def kept_rows(self, B, T):
        """bool [B,T]: which rows hold a token, for a program of the serve
        path that keeps its keys (a bucket, a prefill chunk); None for
        every other program."""
        return None


class Seq(_Mode):
    """Whole sequences [B, T], right-padded to T with `n_valid` [B] real
    tokens each (None: all). keep=False is the plain forward. keep=True
    also leaves in `carry` what the engine needs to go on decoding: the
    state of new_request_state and the keys and values of every layer that
    caches them. `chunk` (start, page_table) makes it ONE sequence's
    prefill chunk: mamba and window layers start from the state in `carry`,
    the layers that cache keys write and read the page pool in `carry`
    (by XLA under `tp` > 1 of `mesh`: GSPMD cannot partition the kernel),
    and only `export` leaves the chunk's own keys and values beside it, in
    the pool's dtype. `window_table` [ring]: a chunk's pages in the window
    page space (`cfg.window_paged`), whose pool `carry` then holds as the
    engine does (`wk`, `wv`). `attend_rows`: how many of a chunk's tokens
    ONE call of the paged chunk kernel takes (0: all of them); a chunk of
    more writes all its keys and then attends block by block, each block
    the call a chunk of that many rows at its position would make."""

    def __init__(self, cfg: ModelConfig, n_valid=None, keep: bool = False,
                 chunk=None, page_size: int = 0, mesh=None,
                 export: bool = False, window_table=None,
                 attend_rows: int = 0, route_counts: bool = False):
        self.cfg, self.n_valid, self.keep, self.chunk = cfg, n_valid, keep, chunk
        self.route_counts = route_counts
        self.ps, self.mesh, self.export = page_size, mesh, export
        self.window_table = window_table
        self.attend_rows = attend_rows
        self.by_xla = mesh is not None and mesh.shape.get("tp", 1) > 1

    def positions(self, T):
        return None if self.chunk is None else (
            self.chunk[0] + jnp.arange(T))[None]

    @property
    def plain(self) -> bool:
        """The plain forward (no cache, no state): what a train step
        differentiates, so what names the values a checkpoint keeps."""
        return self.chunk is None and not self.keep

    def init_carry(self, x, pools=None, state=None) -> Params:
        cfg = self.cfg
        B, T = x.shape[0], x.shape[-2]
        carry, dtype = {}, x.dtype
        if self.route_counts:  # every expert layer's choices, by expert
            carry["route_counts"] = jnp.zeros(
                (cfg.second_halves.count("moe") + cfg.mtp_depth,
                 cfg.router_width), jnp.int32)
        if cfg.count("mamba"):
            carry["mem"] = jnp.zeros((B, T, cfg.ssm_inner), x.dtype)
        if self.chunk is not None:
            carry.update(state, k_pages=pools[0], v_pages=pools[1])
            dtype = pools[0].dtype
            # where the chunk's keys go in the pool
            self.page = self.chunk[1][self.at[0] // self.ps]
            self.slot = self.at[0] % self.ps
            if self.window_table is not None:
                # the ring unrolled over the sequence's pages: page p of
                # the sequence is entry p modulo the ring's width
                ring = self.window_table.shape[0]
                self.unrolled = self.window_table[
                    jnp.arange(self.chunk[1].shape[0]) % ring]
                self.window_page = self.unrolled[self.at[0] // self.ps]
        elif self.keep:
            carry.update(new_request_state(cfg, B, x.dtype))
            if cfg.window_paged:
                # a bucket's window keys, all of them (a bucket is no
                # longer than the window): the engine scatters them to the
                # sequence's pages as it does the other layers' keys
                layers, kv_heads, head_dim = cfg.window_cache_dims
                kv = (layers, B, T, kv_heads, head_dim)
                carry.update(wk=jnp.zeros(kv, dtype), wv=jnp.zeros(kv, dtype))
        if self.chunk is None or self.export:
            layers, kv_heads, head_dim = cfg.cache_dims
            kv = (layers, B, T, kv_heads, head_dim)
            carry.update(k=jnp.zeros(kv, dtype))
            if not cfg.latent_cache:
                carry.update(v=jnp.zeros(kv, dtype))
        return carry

    # -- mamba
    def valid(self, T):
        """[B,T,1] bool: which positions are real tokens (None: all)."""
        if self.n_valid is None:
            return None
        return (jnp.arange(T)[None, :] < self.n_valid[:, None])[..., None]

    def counted(self, B, T):
        """bool [B,T]: the tokens whose choice of experts is counted."""
        valid = self.valid(T)
        return jnp.ones((B, T), bool) if valid is None else valid[..., 0]

    def kept_rows(self, B, T):
        if self.keep or self.chunk is not None:
            return self.counted(B, T)
        return None

    def _lengths(self, B, T):
        return jnp.full((B,), T) if self.n_valid is None else self.n_valid

    def conv(self, carry, mi, u):
        """-> [tail ; u] along time, and the new tail kept."""
        B, T, Di = u.shape
        K = self.cfg.conv_tail[1] + 1
        tail = (carry["conv"][mi].astype(u.dtype) if self.chunk is not None
                else jnp.zeros((B, K - 1, Di), u.dtype))
        ext = jnp.concatenate([tail, u], axis=1)
        if self.keep:
            n = self._lengths(B, T)
            new = jnp.take_along_axis(
                ext, (n[:, None] + jnp.arange(K - 1)[None])[..., None], axis=1)
            carry = {**carry, "conv": carry["conv"].at[mi].set(
                new.astype(carry["conv"].dtype))}
        return ext, carry

    def scan(self, carry, mi, u, dt, A, Bm, Cm, D):
        s0 = (carry["ssm"][mi] if self.chunk is not None
              else jnp.zeros((u.shape[0], *A.shape), _F32))
        y, s1 = ssm_scan(u, dt, A, Bm, Cm, D, s0)
        if self.keep:
            carry = {**carry, "ssm": carry["ssm"].at[mi].set(s1)}
        return y, carry

    def delta(self, carry, gi, q, k, v, g, beta):
        s0 = (carry["gdn"][gi] if self.chunk is not None else jnp.zeros(
            state_shape(1, v.shape[0], *self.cfg.gdn_dims[1:])[1:], _F32))
        o, s1 = gdn_chunk(q, k, v, g, beta, s0)
        if self.keep:
            carry = {**carry, "gdn": carry["gdn"].at[gi].set(s1)}
        return o, carry

    def ssd(self, carry, si, x, dt, A, Bm, Cm):
        s0 = (carry["ssd"][si] if self.chunk is not None else jnp.zeros(
            ssd_state_shape(1, x.shape[0], *self.cfg.ssd_dims[1:4])[1:], _F32))
        y, s1 = ssd_chunk(x, dt, A, Bm, Cm, s0)
        if self.keep:
            carry = {**carry, "ssd": carry["ssd"].at[si].set(s1)}
        return y, carry

    # -- attention
    def _by_rows(self, q, start, attend):
        """A chunk's queries q [C,H,D], the first at position `start`,
        through `attend(rows, first, end)`, the paged chunk kernel over the
        queries `rows` at positions first .. end: `attend_rows` tokens a
        call (the kernel holds a call's query rows as ONE block, and the
        keys of a step shrink as the block grows), the chunk's keys written
        before any call."""
        C, R = q.shape[0], self.attend_rows or q.shape[0]
        if C <= R:
            return attend(q, start, start + C)
        return jnp.concatenate(
            [attend(q[r:r + R], start + r, start + r + R)
             for r in range(0, C, R)], axis=0)

    def attend_window(self, carry, wi, q, k, v, scale):
        cfg = self.cfg
        W = cfg.window
        if self.chunk is None:
            o = _dense_attend(q, k, v, scale, W)
            if self.keep:
                B, T = k.shape[:2]
                n = self._lengths(B, T)
                at = jnp.clip(n[:, None] - W + jnp.arange(W)[None], 0, T - 1)
                for name, new in (("wk", k), ("wv", v)):
                    tail = jnp.take_along_axis(new, at[:, :, None, None], 1)
                    carry = {**carry, name: carry[name].at[wi].set(
                        tail.astype(carry[name].dtype))}
            return o, carry
        # a chunk: the kept tail and the chunk's own keys, side by side,
        # are a little page pool that the chunk kernel reads with the
        # window's bound; keys before the sequence's start are not seen
        start = self.chunk[0]
        C = k.shape[1]
        n_pages = (W + C) // self.ps
        bufs = []
        for name, new in (("wk", k), ("wv", v)):
            buf = jnp.concatenate(
                [carry[name][wi][0].astype(new.dtype), new[0]], axis=0)
            bufs.append(buf)
            carry = {**carry, name: carry[name].at[wi, 0].set(
                jax.lax.dynamic_slice_in_dim(buf, self.n_valid[0], W, 0)
                .astype(carry[name].dtype))}
        # [W+C, KVH, D] holds the pool's own rows in order: no copy
        pool = [b.reshape(pool_shape(1, n_pages, self.ps, *b.shape[1:]))
                for b in bufs]
        o = self._by_rows(q[0], W, lambda q, at, end: paged_attention_chunk(
            q, *pool, jnp.arange(n_pages, dtype=jnp.int32), at, end, 0,
            scale=scale, window=W, first=jnp.maximum(W - start, 0)))
        return o[None].astype(q.dtype), carry

    def attend_paged_window(self, carry, si, q, k, v, scale):
        """Attention over the last `window` keys of a layer whose keys are
        row `si` of the window page space."""
        W = self.cfg.window
        if self.chunk is None:
            if "wk" in carry:
                carry = {**carry, **{
                    name: carry[name].at[si].set(new.astype(carry[name].dtype))
                    for name, new in (("wk", k), ("wv", v))}}
            return _dense_attend(q, k, v, scale, W), carry
        start = self.chunk[0]

        def attend(q, kp, vp, layer):
            return self._by_rows(q, start, lambda q, at, end: (
                paged_attention_chunk(q, kp, vp, self.unrolled, at, end,
                                      layer, scale=scale, window=W)))

        # the chunk's keys into the sequence's ring, then attention over it
        o, wk, wv = write_then_attend(
            attend, q[0], k[0], v[0], carry["wk"], carry["wv"], si,
            self.window_page, self.slot)
        return o[None].astype(q.dtype), {**carry, "wk": wk, "wv": wv}

    def attend_full(self, carry, fi, q, k, v, scale):
        """Attention over every key so far, the layer's own (written as
        row `fi` of what caches them) or, k is None: a cross layer, which
        reads row `fi` and writes nothing."""
        if k is not None and "k" in carry:
            carry = {**carry,
                     "k": carry["k"].at[fi].set(k.astype(carry["k"].dtype)),
                     "v": carry["v"].at[fi].set(v.astype(carry["v"].dtype))}
        if self.chunk is None:
            if k is None:
                k, v = carry["k"][fi], carry["v"][fi]
            return _dense_attend(q, k, v, scale), carry
        start, table = self.chunk

        def attend(q, kp, vp, layer):
            # key j is seen by query row c iff j <= start + c (the prefix
            # and the chunk so far); rows past n_valid write keys that no
            # later position bound lets anything see
            return self._by_rows(q, start, lambda q, at, end: (
                paged_attention_chunk(q, kp, vp, table, at, end, layer,
                                      scale=scale, force_xla=self.by_xla)))

        kp, vp = carry["k_pages"], carry["v_pages"]
        if k is None:
            return attend(q[0], kp, vp, fi)[None].astype(q.dtype), carry
        o, kp, vp = write_then_attend(
            attend, q[0], k[0], v[0], kp, vp, fi, self.page, self.slot)
        return o[None].astype(q.dtype), {**carry, "k_pages": kp, "v_pages": vp}

    def attend_mla(self, carry, fi, q_n, q_r, c, k_r, wk_b, wv_b, scale):
        """Latent attention of the layer whose rows are row `fi` of the
        pool: q_n / q_r [B,T,H,.] the heads' plain and rotary queries, c
        [B,T,L] the tokens' latents, k_r [B,T,R] their shared rotary key,
        wk_b [L,H,N] / wv_b [L,H,V] what carries a latent up to a head's
        keys and values. -> the heads' values [B,T,H,V]."""
        W = self.cfg.latent_row
        row = _latent_row(c, k_r, W)
        if "k" in carry:
            carry = {**carry, "k": carry["k"].at[fi].set(
                row[:, :, None].astype(carry["k"].dtype))}
        if self.chunk is None:
            # no past: each token's keys and values are up-projected once,
            # and heads of N + R meet values of V in the flash kernel
            k = jnp.concatenate([
                jnp.einsum("btl,lhn->bthn", c, wk_b), jnp.broadcast_to(
                    k_r[:, :, None], (*q_r.shape[:-1], k_r.shape[-1]))], axis=-1)
            return _dense_attend(jnp.concatenate([q_n, q_r], axis=-1), k,
                                 jnp.einsum("btl,lhv->bthv", c, wv_b),
                                 scale), carry
        start, table = self.chunk
        # where the chunk's tokens end: a tile of padding rows past it
        # costs the kernel nothing
        total = start + self._lengths(1, c.shape[1])[0]

        def attend(q, pool, layer):
            return latent_attention_chunk(
                q, pool, table, start, total, layer, c.shape[-1], scale)

        o, pool = write_latent_then_attend(
            attend, _absorb(q_n[0], q_r[0], wk_b, W), row[0],
            carry["k_pages"], fi, self.page, self.slot)
        return (_unabsorb(o, wv_b)[None].astype(q_n.dtype),
                {**carry, "k_pages": pool})


class Decode(_Mode):
    """One token for every decode slot [B, 1]: `positions` [B] is where it
    goes, `page_tables` [B, pages] the pages of the layers that cache keys.
    `carry` holds the engine's pools and state whole (new_engine_state +
    the pool). Under `tp` > 1 of `mesh` the paged kernel runs per shard.
    `window_tables` [B, ring]: the slots' pages in the window page space
    (`cfg.window_paged`; None: slot b owns the ring 1 + b * ring ..)."""

    def __init__(self, cfg: ModelConfig, positions, page_tables,
                 page_size: int, mesh=None, window_tables=None):
        self.cfg, self.pos, self.tables, self.ps, self.mesh = (
            cfg, positions, page_tables, page_size, mesh)
        B = positions.shape[0]
        # where this token's keys go in the pool
        self.page = page_tables[jnp.arange(B), positions // page_size]
        self.slot = positions % page_size
        # a slot that holds no sequence has the trash page for a table
        self.live = page_tables[:, 0] > 0
        # the keys a slot attends over, this token's among them: none where
        # no sequence is, and the paged kernel's program then does nothing
        self.lengths = jnp.where(self.live, positions + 1, 0)
        if window_tables is not None:
            self.ring, self.ring_table = window_tables.shape[1], window_tables
        else:
            self.ring = (ring_pages(cfg, page_size) if cfg.count("window")
                         else 1)
            self.ring_table = (
                1 + jnp.arange(B)[:, None] * self.ring
                + jnp.arange(self.ring)[None, :]).astype(jnp.int32)

    def positions(self, T):
        return self.pos[:, None]

    def init_carry(self, x, pools, state) -> Params:
        carry = {**state, "k_pages": pools[0], "v_pages": pools[1]}
        if self.cfg.count("mamba"):
            carry["mem"] = jnp.zeros(
                (*x.shape[:2], self.cfg.ssm_inner), x.dtype)
        return carry

    def valid(self, T):
        return None

    def counted(self, B, T):
        return jnp.broadcast_to(self.live[:, None], (B, T))

    def live_rows(self, T):
        return self.live if T == 1 else None

    def conv(self, carry, mi, u):
        ext = jnp.concatenate([carry["conv"][mi].astype(u.dtype), u], axis=1)
        return ext, {**carry, "conv": carry["conv"].at[mi].set(
            ext[:, 1:].astype(carry["conv"].dtype))}

    def scan(self, carry, mi, u, dt, A, Bm, Cm, D):
        y, ssm = ssm_step(carry["ssm"], mi, u[:, 0], dt[:, 0], A, Bm[:, 0],
                          Cm[:, 0], D)
        return y[:, None], {**carry, "ssm": ssm}

    def delta(self, carry, gi, q, k, v, g, beta):
        o, state = gdn_step(carry["gdn"], gi, q[:, 0], k[:, 0], v[:, 0],
                            g[:, 0], beta[:, 0], self.live)
        return o[:, None], {**carry, "gdn": state}

    def ssd(self, carry, si, x, dt, A, Bm, Cm):
        y, state = ssd_step(carry["ssd"], si, x[:, 0], dt[:, 0], A, Bm[:, 0],
                            Cm[:, 0], self.live)
        return y[:, None], {**carry, "ssd": state}

    def attend_window(self, carry, wi, q, k, v, scale):
        page = jnp.take_along_axis(
            self.ring_table, ((self.pos // self.ps) % self.ring)[:, None], 1)

        def attend(q, kp, vp, layer):
            return paged_attention_decode(
                q, kp, vp, self.ring_table, self.lengths, layer, scale=scale,
                window=self.cfg.window)

        o, wk, wv = write_then_attend(
            attend, q[:, 0], k[:, 0], v[:, 0],
            carry["wk"], carry["wv"], wi, page[:, 0], self.pos % self.ps)
        return o[:, None], {**carry, "wk": wk, "wv": wv}

    # a ring of allocated pages is a ring: what differs is who filled the
    # table
    attend_paged_window = attend_window

    def attend_full(self, carry, fi, q, k, v, scale):
        def attend(q, kp, vp, layer):
            return paged_attention_decode(q, kp, vp, self.tables,
                                          self.lengths, layer, scale=scale,
                                          mesh=self.mesh)

        kp, vp = carry["k_pages"], carry["v_pages"]
        if k is None:
            return attend(q[:, 0], kp, vp, fi)[:, None], carry
        # this token's keys into their page slot, then attention
        o, kp, vp = write_then_attend(
            attend, q[:, 0], k[:, 0], v[:, 0], kp, vp, fi,
            self.page, self.slot)
        return o[:, None], {**carry, "k_pages": kp, "v_pages": vp}

    def attend_mla(self, carry, fi, q_n, q_r, c, k_r, wk_b, wv_b, scale):
        W = self.cfg.latent_row

        def attend(q, pool, layer):
            return latent_attention_decode(
                q, pool, self.tables, self.lengths, layer, c.shape[-1], scale)

        # this token's row into its page slot, then the absorbed form: the
        # row is read once, for its score and its value
        o, pool = write_latent_then_attend(
            attend, _absorb(q_n[:, 0], q_r[:, 0], wk_b, W),
            _latent_row(c, k_r, W)[:, 0], carry["k_pages"], fi,
            self.page, self.slot)
        return _unabsorb(o, wv_b)[:, None], {**carry, "k_pages": pool}


class Verify(Decode):
    """Decode for S tokens a slot [B, S] at `positions` [B] + 0..S-1
    (speculation: the last committed token and its drafts): rows past a
    slot's `n_draft` [B] write to the trash page, and row s sees the keys
    up to its own. Pages only: what keeps state beside its pages cannot
    be rewound to the accepted draft, and the engine refuses it."""

    def __init__(self, cfg: ModelConfig, positions, page_tables,
                 page_size: int, n_draft, mesh=None):
        super().__init__(cfg, positions, page_tables, page_size, mesh)
        self.n_draft = n_draft

    def positions(self, T):
        return self.pos[:, None] + jnp.arange(T)[None, :]

    def live_rows(self, T):
        return None  # S tokens a slot, whatever S: not a step

    def init_carry(self, x, pools, state) -> Params:
        B, S = self.at.shape
        row_valid = jnp.arange(S)[None, :] <= self.n_draft[:, None]
        self.page = jnp.where(
            row_valid,
            self.tables[jnp.arange(B)[:, None], self.at // self.ps], 0)
        self.slot = self.at % self.ps
        return super().init_carry(x, pools, state)

    def attend_full(self, carry, fi, q, k, v, scale):
        def attend(q, kp, vp, layer):
            return paged_attention_verify(q, kp, vp, self.tables, self.pos,
                                          layer, scale=scale, mesh=self.mesh)

        o, kp, vp = write_then_attend(
            attend, q, k, v, carry["k_pages"], carry["v_pages"], fi,
            self.page, self.slot)
        return o, {**carry, "k_pages": kp, "v_pages": vp}

    def attend_mla(self, carry, fi, q_n, q_r, c, k_r, wk_b, wv_b, scale):
        raise NotImplementedError(
            f"{self.cfg.name!r}: no kernel verifies a span of drafts over "
            "a pool of latents (ops/mla_attention.py has decode and chunk)")

    def attend_paged_window(self, carry, si, q, k, v, scale):
        raise NotImplementedError(
            f"{self.cfg.name!r}: Verify is not written over two page "
            "spaces: a rejected draft's keys have overwritten the page "
            "behind the window, which no position rewinds")


# ---------------------------------------------------------------------------
# mixers: one function a kind
# ---------------------------------------------------------------------------


def _mamba(h, lp, cfg, mi, mode, carry):
    dtype = h.dtype
    Di, N, R = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    T = h.shape[1]
    uz = jnp.einsum("btd,de->bte", h, lp["m_in"].astype(dtype))
    u, z = uz[..., :Di], uz[..., Di:]
    ext, carry = mode.conv(carry, mi, u)
    u = jax.nn.silu(_taps(ext, lp["m_conv"], T) + lp["m_conv_b"].astype(_F32))
    xdbc = jnp.einsum("bte,er->btr", u.astype(dtype), lp["m_x"].astype(dtype),
                      preferred_element_type=_F32)
    dt = jax.nn.softplus(
        jnp.einsum("btr,re->bte", xdbc[..., :R].astype(dtype),
                   lp["m_dt"].astype(dtype), preferred_element_type=_F32)
        + lp["m_dt_b"].astype(_F32))
    valid = mode.valid(T)
    if valid is not None:
        dt = jnp.where(valid, dt, 0.0)  # padding leaves the state alone
    y, carry = mode.scan(
        carry, mi, u, dt, -jnp.exp(lp["m_A_log"].astype(_F32)),
        xdbc[..., R:R + N], xdbc[..., R + N:], lp["m_D"].astype(_F32))
    out = jnp.einsum("bte,ed->btd",
                     (y * jax.nn.silu(z.astype(_F32))).astype(dtype),
                     lp["m_out"].astype(dtype))
    return out, {**carry, "mem": y.astype(dtype)}


def _taps(ext, w, T):
    """The causal depthwise convolution both convolution mixers run: ext
    [B, K-1+T, C] is [tail ; inputs] along time, w [K, C]; tap K-1
    multiplies the current position. -> float32 [B, T, C]."""
    w = w.astype(_F32)
    return sum(ext[:, j:j + T].astype(_F32) * w[j] for j in range(w.shape[0]))


def _short_conv(h, lp, cfg, ci, mode, carry):
    """The gated short convolution: the state is the mode's conv tail, as
    the mamba layer's is, here the last taps - 1 rows of B * x."""
    dtype = h.dtype
    D, T = cfg.d_model, h.shape[1]
    bcx = jnp.einsum("btd,de->bte", h, lp["c_in"].astype(dtype))
    b, c, x = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    ext, carry = mode.conv(carry, ci, b * x)
    z = c.astype(_F32) * _taps(ext, lp["c_conv"], T)
    return jnp.einsum("bte,ed->btd", z.astype(dtype),
                      lp["c_out"].astype(dtype)), carry


def _gdn(h, lp, cfg, gi, mode, carry):
    """The gated delta rule: the state is the mode's conv tail (over the
    q, k and v channels side by side) and its delta-rule state matrix."""
    dtype = h.dtype
    B, T, _ = h.shape
    _, H, dk, dv = cfg.gdn_dims
    qkv = jnp.einsum("btd,de->bte", h, lp["d_in"].astype(dtype))
    ext, carry = mode.conv(carry, gi, qkv)
    qkv = jax.nn.silu(_taps(ext, lp["d_conv"], T))

    def unit(x):  # L2 over a head's lanes
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k, v = (x.reshape(B, T, H, -1)
               for x in jnp.split(qkv, [H * dk, 2 * H * dk], axis=-1))
    q, k = unit(q) * dk ** -0.5, unit(k)
    if cfg.gdn_channel_rank:
        # a decay a key channel: the low-rank pair, A_log a head, dt_bias
        # a lane; beta is a projection of its own
        fa = jnp.einsum("btd,dr->btr", h, lp["d_fa"].astype(dtype))
        f = jnp.einsum("btr,re->bte", fa, lp["d_fb"].astype(dtype),
                       preferred_element_type=_F32)
        g = -jnp.exp(lp["d_A_log"].astype(_F32))[:, None] * jax.nn.softplus(
            f + lp["d_dt_b"].astype(_F32)).reshape(B, T, H, dk)
        b = jnp.einsum("btd,de->bte", h, lp["d_b"].astype(dtype),
                       preferred_element_type=_F32)
    else:
        ab = jnp.einsum("btd,de->bte", h, lp["d_ab"].astype(dtype),
                        preferred_element_type=_F32)
        g = -jnp.exp(lp["d_A_log"].astype(_F32)) * jax.nn.softplus(
            ab[..., :H] + lp["d_dt_b"].astype(_F32))
        b = ab[..., H:]
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.gdn_neg_eigval else 1.0)
    valid = mode.valid(T)
    if valid is not None:  # padding leaves the state alone
        g = jnp.where(valid[..., None] if g.ndim == 4 else valid, g, 0.0)
        beta = jnp.where(valid, beta, 0.0)
    o, carry = mode.delta(carry, gi, q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    if cfg.gdn_gate_rank:  # a low-rank pair with a bias, under a sigmoid
        ga = jnp.einsum("btd,dr->btr", h, lp["d_ga"].astype(dtype))
        gate = jnp.einsum("btr,re->bte", ga, lp["d_gb"].astype(dtype),
                          preferred_element_type=_F32) + lp["d_gb_b"].astype(_F32)
        act = jax.nn.sigmoid
    else:
        gate = jnp.einsum("btd,de->bte", h, lp["d_gate"].astype(dtype))
        act = jax.nn.silu
    y = (o * lp["d_norm"].astype(_F32)).reshape(B, T, H * dv) \
        * act(gate.astype(_F32))
    return jnp.einsum("bte,ed->btd", y.astype(dtype),
                      lp["d_out"].astype(dtype)), carry


def _ssd(h, lp, cfg, si, mode, carry):
    """The scalar-decay state-space mixer: the state is the mode's conv
    tail (over x and every group's B and C side by side) and its
    state-space state matrix."""
    dtype = h.dtype
    B, T, _ = h.shape
    _, H, P, N, G = cfg.ssd_dims
    Di = H * P
    zxbc = jnp.einsum("btd,de->bte", h, lp["s_in"].astype(dtype))
    z, xbc = zxbc[..., :Di], zxbc[..., Di:]
    ext, carry = mode.conv(carry, si, xbc)
    xbc = jax.nn.silu(_taps(ext, lp["s_conv"], T)
                      + lp["s_conv_b"].astype(_F32))
    x = xbc[..., :Di].reshape(B, T, H, P)
    Bm, Cm = (a.reshape(B, T, G, N)
              for a in jnp.split(xbc[..., Di:], 2, axis=-1))
    dt = jax.nn.softplus(
        jnp.einsum("btd,de->bte", h, lp["s_dt"].astype(dtype),
                   preferred_element_type=_F32) + lp["s_dt_b"].astype(_F32))
    valid = mode.valid(T)
    if valid is not None:
        dt = jnp.where(valid, dt, 0.0)  # padding leaves the state alone
    y, carry = mode.ssd(carry, si, x, dt,
                        -jnp.exp(lp["s_A_log"].astype(_F32)), Bm, Cm)
    y = (y + lp["s_D"].astype(_F32)[:, None] * x).reshape(B, T, Di)
    # the gate first, then the norm, over the lanes of a group's heads
    y = (y * jax.nn.silu(z.astype(_F32))).reshape(B, T, G, Di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
    y = y.reshape(B, T, Di) * lp["s_norm"].astype(_F32)
    return jnp.einsum("bte,ed->btd", y.astype(dtype),
                      lp["s_out"].astype(dtype)), carry


def _turn(x, at, theta, yarn=None):
    """x [B,T,heads,R] turned to the tokens' positions `at` ([B,T]; None:
    0..T-1): interleaved pairs (2i, 2i+1) by at * theta^(-2i/R), or by
    yarn's blend of that and its interpolation (`yarn`: cfg.rope_yarn),
    float32 inside (XLA fuses it into the projection)."""
    B, T, _, R = x.shape
    if yarn is None:
        inv = 1.0 / theta ** (jnp.arange(0, R, 2, dtype=_F32) / R)
    else:
        inv = yarn_inv_freq(R, theta, *yarn[:4])
    at = jnp.arange(T)[None] if at is None else at
    ang = at.astype(_F32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None and yarn[4] != yarn[5]:  # the tables' own factor
        grow = yarn_mscale(yarn[0], yarn[4]) / yarn_mscale(yarn[0], yarn[5])
        cos, sin = cos * grow, sin * grow
    xf = x.astype(_F32).reshape(*x.shape[:-1], R // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _scaled_rms(x, w, eps, scale):
    """RMSNorm over the last axis, times its weight and `scale`; float32
    inside, so the scale is not rounded twice."""
    xf = x.astype(_F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(_F32) * scale).astype(x.dtype)


def _latent_row(c, k_r, W):
    """[.., L] and [.., R] -> a token's row of the pool [.., W]."""
    pad = jnp.zeros((*c.shape[:-1], W - c.shape[-1] - k_r.shape[-1]), c.dtype)
    return jnp.concatenate([c, k_r.astype(c.dtype), pad], axis=-1)


def _absorb(q_n, q_r, wk_b, W):
    """The heads' queries carried into the latent's space and laid out as
    a row of the pool is: [.., H, N], [.., H, R] -> [.., H, W]."""
    return _latent_row(jnp.einsum("...hn,lhn->...hl", q_n, wk_b), q_r, W)


def _unabsorb(o, wv_b):
    """A head's weighted sum of latents [.., H, L] -> its values [.., H, V]."""
    return jnp.einsum("...hl,lhv->...hv", o, wv_b)


def _mla(h, lp, cfg, fi, mode, carry):
    """One block's latent attention over h [B,T,D] (`lp`: the block's own
    leaves, by their plain names); its rows are row `fi` of the pool. The
    queries come through a normed bottleneck (`wq_a`, `q_ln`, `wq_b`) or,
    where the model has none (`cfg.q_lora_rank` 0), from ONE projection
    `wq`."""
    dtype = h.dtype
    D, N = cfg.d_model, cfg.qk_nope_dim
    eps, theta = cfg.norm_eps, cfg.rope_theta

    def up(rank):
        return (D / rank) ** 0.5 if cfg.mla_scale_lora else 1.0

    # a train step's checkpoint keeps the three bottlenecks by name (the
    # plain forward alone names them: a name moves the numbers in a lowered
    # serve program's private function names)
    named = checkpoint_name if mode.plain else (lambda a, name: a)
    cq_name, c_name, kr_name = _LATENT_NAMES
    yarn = cfg.rope_yarn
    if cfg.q_lora_rank:
        cq = jnp.einsum("btd,dr->btr", h, lp["wq_a"].astype(dtype))
        cq = named(_scaled_rms(cq, lp["q_ln"], eps, up(cfg.q_lora_rank)),
                   cq_name)
        q = jnp.einsum("btr,rhk->bthk", cq, lp["wq_b"].astype(dtype))
    else:
        q = jnp.einsum("btd,dhk->bthk", h, lp["wq"].astype(dtype))
    c = _scaled_rms(jnp.einsum("btd,dr->btr", h, lp["wkv_a"].astype(dtype)),
                    lp["kv_ln"], eps, up(cfg.kv_lora_rank))
    c = named(c, c_name)
    k_r = jnp.einsum("btd,dr->btr", h, lp["wkr"].astype(dtype))
    k_r = named(_turn(k_r[:, :, None], mode.at, theta, yarn)[:, :, 0], kr_name)
    scale = (N + cfg.qk_rope_dim) ** -0.5
    if yarn is not None:  # the score's own factor, squared (q's and k's)
        scale *= yarn_mscale(yarn[0], yarn[5]) ** 2
    o, carry = mode.attend_mla(
        carry, fi, q[..., :N], _turn(q[..., N:], mode.at, theta, yarn), c,
        k_r, lp["wk_b"].astype(dtype), lp["wv_b"].astype(dtype), scale)
    return jnp.einsum("bthv,hvd->btd", o.astype(dtype),
                      lp["wo"].astype(dtype)), carry


def _block_leaves(lp, i):
    """Block i's leaves of a double layer, by their plain names."""
    tag = str(i)
    return {n[:-1]: w for n, w in lp.items() if n.endswith(tag)}


def _mla2(x, lp, cfg, idx, mode, carry):
    """The double layer (module docstring). The scopes are the other
    layers': "attn", then "moe" or "ffn"."""
    shortcut = None
    for i in (0, 1):
        bp = _block_leaves(lp, i)
        with jax.named_scope("attn"):
            o, carry = _mla(_norm(x, bp["a_ln"], None, cfg), bp, cfg,
                            2 * idx + i, mode, carry)
            x = x + o
        b = _norm(x, bp["p_ln"], None, cfg)
        if i == 0:
            with jax.named_scope("moe"):
                shortcut, carry = _experts(b, lp, cfg, None, mode, carry)
        with jax.named_scope("ffn"):
            x = x + _dense_ffn(b, {"w_in": bp["f_in"], "w_gate": bp["f_gate"],
                                   "w_out": bp["f_out"]}, cfg)
    return x + shortcut, carry


def _count_choices(carry, ids, cfg, mode):
    """Where the counted tokens' choices ids [B,T,k] fell, added to
    `choices` [2] where the carry holds it: on identity experts, on held
    ones (the rest fell on experts held elsewhere)."""
    if "choices" not in carry:
        return carry
    counted = mode.counted(*ids.shape[:2])[..., None]
    held = (ids >= cfg.experts_first) & (
        ids < cfg.experts_first + cfg.num_experts)
    fell = jnp.stack([jnp.sum((ids >= cfg.experts_routed) & counted),
                      jnp.sum(held & counted)])
    return {**carry, "choices": carry["choices"] + fell.astype(_F32)}


def _gmu(h, lp, cfg, carry):
    dtype = h.dtype
    g = jnp.einsum("btd,de->bte", h, lp["g_in"].astype(dtype))
    y = carry["mem"].astype(_F32) * jax.nn.silu(g.astype(_F32))
    return jnp.einsum("bte,ed->btd", y.astype(dtype), lp["g_out"].astype(dtype))


def _project(h, lp, w, b):
    return (jnp.einsum("btd,dhk->bthk", h, lp[w].astype(h.dtype))
            + lp[b].astype(h.dtype))


def _attention(h, lp, cfg, kind, layer, idx, mode, carry):
    """window / full / cross: differential attention through the modes'
    plain attends, with heads twice as wide (module docstring)."""
    dtype = h.dtype
    B, T, _ = h.shape
    H, hd = cfg.n_heads, cfg.hdim
    q = _project(h, lp, "wq", "bq")
    zero = jnp.zeros_like(q)
    even = (jnp.arange(H) % 2 == 0)[:, None]
    q = jnp.where(even, jnp.concatenate([q, zero], -1),
                  jnp.concatenate([zero, q], -1))
    k = v = None
    if kind != "cross":  # pairs (2g, 2g+1) side by side: one row a pair
        k = _project(h, lp, "wk", "bk").reshape(
            B, T, cfg.pool_heads, cfg.pool_dim)
        v = _project(h, lp, "wv", "bv").reshape(
            B, T, cfg.pool_heads, cfg.pool_dim)
    attend = mode.attend_window if kind == "window" else mode.attend_full
    o, carry = attend(carry, idx, q, k, v, hd ** -0.5)
    o = o.reshape(B, T, H // 2, 2, 2 * hd).astype(_F32)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, _F32))
    lam = (jnp.exp(jnp.sum(lp["lam_q1"].astype(_F32) * lp["lam_k1"].astype(_F32)))
           - jnp.exp(jnp.sum(lp["lam_q2"].astype(_F32) * lp["lam_k2"].astype(_F32)))
           + lam0)
    a = o[..., 0, :] - lam * o[..., 1, :]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + cfg.norm_eps)
    o = (a * lp["sub_w"].astype(_F32) * (1.0 - lam0)).reshape(B, T, H, hd)
    return (jnp.einsum("bthk,hkd->btd", o.astype(dtype), lp["wo"].astype(dtype))
            + lp["bo"].astype(dtype)), carry


def _attn(h, lp, cfg, idx, mode, carry, window=False):
    """The one-block models' mixer: q, k, v turned to the tokens' positions
    (models/transformer.py's, the training block's too), then the mode's
    attention over the layer's own keys: every one of them, or (`window`:
    the "swa" kind, rotary whatever the model says of its other layers)
    the last `cfg.window` in the window page space."""
    q, k, v = _qkv(h, lp, cfg, mode.rope, mode.at, True if window else None)
    if mode.plain:  # what the layer loop's checkpoint keeps (`run_stack`)
        q, k, v = (checkpoint_name(a, n) for a, n in (
            (q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
    attend = mode.attend_paged_window if window else mode.attend_full
    scale = (cfg.hdim ** -0.5 if cfg.attention_multiplier is None
             else cfg.attention_multiplier)
    o, carry = attend(carry, idx, q, k, v, scale)
    if cfg.attn_gate:  # from the mixer's own input, lane by lane
        gate = jnp.einsum("btd,dhk->bthk", h, lp["wg"].astype(h.dtype))
        o = o.astype(_F32) * jax.nn.sigmoid(gate.astype(_F32))
    return jnp.einsum("bthk,hkd->btd", o.astype(h.dtype),
                      lp["wo"].astype(h.dtype)), carry


def _count_routes(carry, ids, cfg, lp):
    """How many of the tokens' choices ids [B,T,k] fell on each of the
    router's outputs, written to this expert layer's row of `route_counts`
    where the carry holds it (a train step's forward: `lp["moe_index"]`,
    the layer's place among the expert layers)."""
    if "route_counts" not in carry:
        return carry
    n = jnp.sum(jax.nn.one_hot(ids, cfg.router_width, dtype=jnp.int32),
                axis=(0, 1, 2))
    return {**carry, "route_counts": jax.lax.dynamic_update_index_in_dim(
        carry["route_counts"], n, lp["moe_index"], 0)}


def _count_touched(carry, visited):
    """The experts a step's product visited (those that at least one live
    row chose: `moe_ffn_step`'s own list), added to `touched` [1] where the
    carry holds it (a decode span's)."""
    if "touched" not in carry:
        return carry
    return {**carry, "touched": carry["touched"] + visited.astype(_F32)}


def _experts(h, lp, cfg, gate, mode, carry):
    """The expert layer over the normed rows h [B,T,D] in the form the
    program's static shape allows (models/transformer.py `_moe_ffn`): a
    STEP, where the mode knows its live rows, visits the experts they chose
    and no others; a bucket or a chunk, where the mode knows the rows that
    hold a token, runs each expert over the rows that chose it
    (`lp["experts"]`, which `run_stack` hands such programs); every other
    program runs the forms it ran. -> (y, carry with what it counts)."""
    live = mode.live_rows(h.shape[1])
    if "experts" in lp and live is not None:
        y, ids, visited = moe_ffn_step(h, lp, cfg, gate, live)
        carry = _count_touched(carry, visited)
    elif "experts" in lp:
        y, ids = moe_ffn_groups(h, lp, cfg, gate,
                                mode.kept_rows(*h.shape[:2]))
    elif cfg.counts_choices or "route_counts" in carry:
        # a share layer (where the choices fell), a train step (how many
        # chose each expert): the form `moe_grouped` picks
        y, _, ids = moe_ffn_ids(h, lp, cfg, gate)
    else:
        return _moe_ffn(h, lp, cfg, gate)[0], carry
    return y, _count_choices(_count_routes(carry, ids, cfg, lp), ids, cfg, mode)


def _layer(x, lp, cfg, kind, half, layer, idx, mode, carry):
    if kind == "mla2":
        return _mla2(x, lp, cfg, idx, mode, carry)
    gate = None
    if cfg.router_input == "layer" and half == "moe":
        # the router reads the layer's input: the choice is made before
        # the mixer runs, and the experts wait for nothing but their rows
        with jax.named_scope("route"):
            gate = _moe_gate(x, lp, cfg)
    # the scopes are what a profile's readers key on: the mixer's kind
    # ("attn" as in the training block), then "ffn" or "moe"
    place = cfg.norm_place

    def mixer(x):
        h = x if place == "post" else _norm(x, lp["ln1"], lp.get("ln1_b"), cfg)
        if kind in ("attn", "swa"):
            o, c = _attn(h, lp, cfg, idx, mode, carry, kind == "swa")
        elif kind == "mla":
            o, c = _mla(h, lp, cfg, idx, mode, carry)
        elif kind == "gdn":
            o, c = _gdn(h, lp, cfg, idx, mode, carry)
        elif kind == "ssd":
            o, c = _ssd(h, lp, cfg, idx, mode, carry)
        elif kind == "conv":
            o, c = _short_conv(h, lp, cfg, idx, mode, carry)
        elif kind == "mamba":
            o, c = _mamba(h, lp, cfg, idx, mode, carry)
        elif kind == "gmu":
            o, c = _gmu(h, lp, cfg, carry), carry
        else:
            o, c = _attention(h, lp, cfg, kind, layer, idx, mode, carry)
        if place == "post":
            o = _norm(o, lp["ln1"], lp.get("ln1_b"), cfg)
        elif place == "both":
            o = _norm(o, lp["ln1_post"], None, cfg)
        if cfg.residual_multiplier != 1.0:
            o = o * cfg.residual_multiplier
        return o, c

    with jax.named_scope(kind):
        x, carry = _residual(x, lp, cfg, "hc1", mixer,
                             HC_OUT_NAME if mode.plain else None)
        if mode.plain and cfg.hc_streams == 1:
            x = checkpoint_name(x, "attn_half")
    if half == "moe":
        if "route_counts" in carry:  # the layer's place among expert layers
            lp = {**lp, "moe_index": layer - cfg.n_dense_layers}
        return _ffn_half(x, lp, cfg, True, lambda h: _experts(
            h, lp, cfg, gate, mode, carry))
    return _ffn_half(x, lp, cfg, False)[0], carry


def run_stack(layers, x, cfg: ModelConfig, mode, carry):
    """Every layer of the stack over x [B,T,D] -> (x, carry). `layers` is
    one entry a segment of `cfg.segments()` (a tuple with one stacked dict
    per layer of the period), or the one-block models' stacked dict, their
    one segment. A segment of r > 1 periods is one `lax.scan`; which attn,
    conv, mamba, window, full, gdn, ssd or latent layer a layer is (its row
    in the state arrays and pools) is counted from the layers before it."""
    if isinstance(layers, dict):
        layers = [(layers,)]
    seen = dict.fromkeys(_COUNTED, 0)
    B, T = x.shape[0], x.shape[-2]  # [B,T,D], or [B,n,T,D] under n streams
    mesh = _current_mesh()
    lifts = ((mode.live_rows(T) is not None and moe_step_visits(cfg, mesh))
             or (mode.kept_rows(B, T) is not None
                 and moe_seq_groups(cfg, B, T, mesh)))
    for (first, kinds, repeats), seg in zip(cfg.segments(), layers):
        per = {k: kinds.count(k) for k in seen}
        stacks = (None,) * len(kinds)
        if lifts:
            # the kernels of a step, a bucket and a chunk read a layer's
            # experts where they lie, in the segment's stacks (a slice, the
            # scan's or `a[0]`, before a custom call is a copy of every
            # expert, every call): they go to the layers whole, beside the
            # layer's index in them
            stacks = tuple(
                {n: lp[n] for n in _EXPERT_LEAVES}
                if cfg.second_halves[first + i] == "moe" else None
                for i, lp in enumerate(seg))
            seg = tuple({n: a for n, a in lp.items() if n not in (held or ())}
                        for lp, held in zip(seg, stacks))

        def period(c, xs, first=first, kinds=kinds, base=dict(seen), per=per,
                   stacks=stacks):
            x, carry = c
            lps, rep = xs
            idx = {k: base[k] + rep * per[k] for k in base}
            for i, (kind, lp) in enumerate(zip(kinds, lps)):
                if stacks[i] is not None:
                    lp = {**lp, "experts": (stacks[i], rep)}
                # a cross layer reads the newest full layer's cache
                at = idx["full"] - 1 if kind == "cross" else idx.get(kind)
                x, carry = _layer(x, lp, cfg, kind,
                                  cfg.second_halves[first + i],
                                  first + rep * len(kinds) + i, at, mode, carry)
                if kind in idx:
                    idx[kind] = idx[kind] + 1
            return (x, carry), None

        if mode.plain and cfg.remat:
            # a train step's layers: the backward recomputes a period but
            # for `KEPT_UNDER_REMAT`
            period = _remat(period, cfg, KEPT_UNDER_REMAT)
            if first == 0:
                from ..util import profiler

                profiler.publish_remat_kept(kept_bytes(cfg, B, T, mesh))
        if repeats == 1:
            (x, carry), _ = period(
                (x, carry), (jax.tree.map(lambda a: a[0], seg), 0))
        else:
            (x, carry), _ = jax.lax.scan(period, (x, carry),
                                         (seg, jnp.arange(repeats)))
        for k in seen:
            seen[k] += per[k] * repeats
    return x, carry


# ---------------------------------------------------------------------------
# whole-sequence entry points
# ---------------------------------------------------------------------------


def _run(params: Params, tokens: jax.Array, cfg: ModelConfig, mode, *carried):
    """tokens [B,T] embedded and through every layer -> (x [B,T,D], the
    residual streams summed where there are several, carry)."""
    x = mode.embed(params, tokens)
    x, carry = run_stack(params["layers"], x, cfg, mode,
                         mode.init_carry(x, *carried))
    carry.pop("mem", None)
    return _collapse(x, cfg), carry


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
            route_counts: bool = False, mtp_tokens=None):
    """tokens [B,T] -> (logits [B,T,V] float32, 0): no cache, no state.
    What `loss_fn` differentiates: under `cfg.remat` each period of layers
    is a checkpoint that keeps `KEPT_UNDER_REMAT`. `route_counts`: one more
    result, int32 [expert layers, router outputs]: how many of the batch's
    choices fell on each expert, layer by layer (a train step's, for the
    router's bias and the counters; the prediction block's experts are the
    last row). `mtp_tokens` [B,T] (a model with `cfg.mtp_depth`: each
    position's NEXT token, a batch's `targets`): one more result, the
    prediction block's logits [B,T,V] for the token after next."""
    mode = Seq(cfg, route_counts=route_counts)
    x, carry = _run(params, tokens, cfg, mode)
    after = ()
    if mtp_tokens is not None:  # its experts' choices join the carry's
        after, carry = _mtp(params, x, mtp_tokens, cfg, mode, carry)
        after = (after,)
    counts = (carry["route_counts"],) if route_counts else ()
    return (_lm_head(x, params, cfg), jnp.zeros((), _F32), *counts, *after)


def _mtp(params: Params, h, next_tokens, cfg: ModelConfig, mode, carry):
    """The multi-token prediction block (DeepSeek-V3 2.2, depth 1) over the
    trunk's stream h [B,T,D] (before the final norm; the residual streams
    already summed): g = W_p [N_h(h) ; N_e(E[next token])], copied into the
    residual streams, through ONE more layer of the stack's last kind with
    experts and parameters of its own, summed, its own final norm and the
    SHARED head -> (logits [B,T,V] for the token after next, carry: the
    block's expert choices in the last row of `route_counts`)."""
    mp, dtype = params["mtp"], h.dtype
    with jax.named_scope("mtp"):
        e = _embed_lookup(params["embed"], next_tokens, dtype, mesh=mode.mesh)
        if cfg.embedding_multiplier != 1.0:
            e = e * cfg.embedding_multiplier
        g = jnp.concatenate([_norm(h, mp["h_norm"], None, cfg),
                             _norm(e, mp["e_norm"], None, cfg)], axis=-1)
        g = jnp.einsum("btk,kd->btd", g, mp["proj"].astype(dtype))
        kind = cfg.layer_kinds[-1]
        # the block holds no cache rows: the trunk's kept keys stay behind
        carry = {k: v for k, v in carry.items() if k not in ("k", "v")}

        def block(c, lp):
            x, carry = c
            return _layer(x, lp, cfg, kind, "moe", cfg.n_layers, 0, mode,
                          carry), None

        (x, carry), _ = _remat(block, cfg, KEPT_UNDER_REMAT)(
            (_expand(g, cfg), carry), mp["layer"])
        return _lm_head(_collapse(x, cfg), {
            **params, "final_norm": mp["final_norm"]}, cfg), carry


# ---------------------------------------------------------------------------
# training: logical axes, what a checkpoint keeps, the router's bias
# ---------------------------------------------------------------------------

# leaf -> logical axes (parallel/sharding.py's rules: embed -> fsdp, heads /
# mlp / expert_mlp / vocab -> tp, expert -> ep), without the leading axis of
# a segment's repeats, which no rule shards
_LEAF_AXES = {
    "wq": ("embed", "heads", None), "wk": ("embed", "heads", None),
    "wv": ("embed", "heads", None), "wg": ("embed", "heads", None),
    "wo": ("heads", None, "embed"), "router": ("embed", None),
    "sh_in": ("embed", "mlp"), "sh_gate": ("embed", "mlp"),
    "sh_out": ("mlp", "embed"),
    # the latent kind: the bottlenecks' down-projections by their input, the
    # up-projections by their heads
    "wq_a": ("embed", None), "wkv_a": ("embed", None), "wkr": ("embed", None),
    "wq_b": (None, "heads", None), "wk_b": (None, "heads", None),
    "wv_b": (None, "heads", None),
}
_HALF_AXES = {
    "ffn": {"w_in": ("embed", "mlp"), "w_gate": ("embed", "mlp"),
            "w_out": ("mlp", "embed")},
    "moe": {"w_in": ("expert", "embed", "expert_mlp"),
            "w_gate": ("expert", "embed", "expert_mlp"),
            "w_out": ("expert", "expert_mlp", "embed")},
}


def param_axes(cfg: ModelConfig) -> Params:
    """Logical axes of `init_params`' tree, leaf for leaf, for a stack that
    can be trained (every kind in `config.TRAINABLE_KINDS`; the others are
    refused by name). Vectors (norms, the router's bias) are replicated."""
    if cfg.untrainable:
        raise NotImplementedError(cfg.untrainable)

    def layer(kind, half):
        axes = {}
        for name, (shape, _) in layer_shapes(cfg, kind, half).items():
            known = _HALF_AXES[half].get(name) or _LEAF_AXES.get(name)
            axes[name] = (None, *(known or (None,) * len(shape)))
        return axes

    out = {"embed": ("vocab", "embed"), "final_norm": ("norm",),
           "layers": [tuple(layer(kind, cfg.second_halves[first + i])
                            for i, kind in enumerate(kinds))
                      for first, kinds, _ in cfg.segments()]}
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    if cfg.norm == "layernorm":
        out["final_norm_b"] = ("norm",)
    if cfg.mtp_depth:  # one layer, unstacked: no leading axis of repeats
        block = layer(cfg.layer_kinds[-1], "moe")
        out["mtp"] = {"layer": {n: a[1:] for n, a in block.items()},
                      "h_norm": ("norm",), "e_norm": ("norm",),
                      "final_norm": ("norm",), "proj": (None, "embed")}
    return out


def kept_bytes(cfg: ModelConfig, B: int, T: int, mesh=None) -> Dict[str, int]:
    """name -> the bytes ONE device keeps under it for a train step over B
    rows of T tokens (`KEPT_UNDER_REMAT`): the attention half in every
    layer, a row a position; the grouped experts' up products in every
    expert layer, a row of the sorted buffer each, and their combined
    result, a row a position (none where the experts take another form). A
    latent layer keeps its three bottlenecks in q's, k's and v's place, and
    its flash output at the kernel's padded width; under several residual
    streams a layer keeps the attention sublayer's output and both
    sublayers' raw coefficients in the attention half's place. The
    prediction block is one more expert layer."""
    positions = -(-B * T // split_ways(("batch", "seq"), mesh))
    act = jnp.dtype(cfg.dtype).itemsize
    grouped = moe_grouped(cfg, B, T, mesh)
    rows = grouped[1] if grouped else 0
    widths = {**_kept_widths(cfg), **dict.fromkeys(_STACK_NAMES, 0)}
    if cfg.latent_cache:
        H, hd = cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim
        wide = -(-max(hd, cfg.v_head_dim) // 128) * 128
        flash_out, flash_lse = FLASH_RESIDUAL_NAMES
        widths.update({
            flash_out: H * wide * act, flash_lse: 4 * H, "attn_q": 0,
            "attn_k": 0, "attn_v": 0, "mla_cq": cfg.q_lora_rank * act,
            "mla_c": cfg.kv_lora_rank * act, "mla_kr": cfg.qk_rope_dim * act})
    n = cfg.hc_streams
    if n > 1:  # float32 coefficients, both sublayers'
        widths.update({"attn_half": 0, HC_OUT_NAME: cfg.d_model * act,
                       HC_COEF_NAME: 2 * (n * n + 2 * n) * 4})
    out = {name: (cfg.n_layers + cfg.mtp_depth) * positions * widths[name]
           for name in _KEPT_UNDER_REMAT + _STACK_NAMES}
    up, gate, combined = GROUPED_RESIDUAL_NAMES
    layers = cfg.second_halves.count("moe") + cfg.mtp_depth
    out.update({up: layers * rows * cfg.expert_ff * act,
                gate: layers * rows * cfg.expert_ff * act,
                combined: layers * positions * cfg.d_model * act * bool(rows)})
    if n > 1 and rows:  # float32 weights and int32 choices, k a token
        out.update(dict.fromkeys(
            MOE_CHOICE_NAMES,
            layers * positions * cfg.num_selected_experts * 4))
    return out


def expert_layers(cfg: ModelConfig):
    """(segment, place in its period, int32 [repeats]: the rows of
    `route_counts` its repeats wrote) for every expert layer's leaves."""
    for s, (first, kinds, repeats) in enumerate(cfg.segments()):
        for i in range(len(kinds)):
            if cfg.second_halves[first + i] == "moe":
                yield s, i, (first + i - cfg.n_dense_layers
                             + len(kinds) * jnp.arange(repeats))


def move_router_bias(params: Params, counts, cfg: ModelConfig) -> Params:
    """After the optimizer's update: every expert's bias a step of
    `cfg.router_bias_rate` towards an even load,
    b_e += rate * sign(mean(n) - n_e), n_e = counts[layer, e] the step's
    choices of expert e among ALL the router's outputs (a chip that holds a
    share routes over all of them). The bias enters the choice alone, so it
    has no gradient; this is the only thing that moves it."""
    n = counts.astype(_F32)
    step = cfg.router_bias_rate * jnp.sign(
        jnp.mean(n, axis=1, keepdims=True) - n)
    layers = [list(seg) for seg in params["layers"]]
    for s, i, rows in expert_layers(cfg):
        lp = layers[s][i]
        layers[s][i] = {**lp, "router_bias": lp["router_bias"]
                        + step[rows].astype(lp["router_bias"].dtype)}
    params = {**params, "layers": [tuple(seg) for seg in layers]}
    if cfg.mtp_depth:  # the prediction block's experts: the last row
        lp = params["mtp"]["layer"]
        params["mtp"] = {**params["mtp"], "layer": {
            **lp, "router_bias": lp["router_bias"]
            + step[-1].astype(lp["router_bias"].dtype)}}
    return params


def run_paged(params: Params, tokens: jax.Array, cfg: ModelConfig, mode,
              pools, state=None):
    """What the engine's decode and chunk programs and the speculative
    programs share: tokens [B,T] through every layer over the page pool
    `pools` (k, v) and `state` (per slot for Decode, one sequence's for a
    Seq chunk; None or the empty tree where pages are all there is).
    -> (x [B,T,D] before the final norm, k_pages, v_pages, state, and
    whatever else the mode kept: an export's `k` and `v`)."""
    x, carry = _run(params, tokens, cfg, mode, pools, state or {})
    return x, carry.pop("k_pages"), carry.pop("v_pages"), carry


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            true_len: jax.Array):
    """The engine's bucket prefill: tokens [B,T] right-padded, true_len [B].
    -> (hidden state [B,T,D] before the final norm, cache): the keys and
    values `k`, `v` [layers,B,T,KVH,D] of the layers that cache them and
    the state of new_request_state, every leaf with the batch on axis 1."""
    return _run(params, tokens, cfg, Seq(cfg, n_valid=true_len, keep=True))
