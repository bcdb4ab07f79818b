"""The family of `xing4.0-29b-a4b` (XingChen-AGI/Xing4.0-29B-A4B, `model_type`
xing4_0): the DeepSeek-V3 block (latent attention with a bottleneck on the
queries, leading dense layers, then a share of many small experts chosen by
sigmoid score + bias beside one shared expert) inside FOUR residual streams
mixed round every sublayer (manifold-constrained hyper-connections,
arXiv:2512.24880), rotary lanes under yarn, and a multi-token prediction
block in the loss. The family's cells TRAIN it: one chip's share of each
layer (the held experts, an eighth of the vocabulary). Its plain reference
is benchmark/reference/xing4.py.

What a family file holds: benchmark/families/mistral.py states the contract.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.families import trinity_afmoe as afmoe
from benchmark.flops import BF16
from benchmark.reference import xing4 as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
modes = ref.EQUATION_MODES + ref.PRECISION_MODES
# what each block of the compared gradient (of L = the main head's loss +
# the weighted prediction block's) is multiplied by, on both sides: every
# layer's first norm weight and the prediction block's [L + 1, D]; every
# residual path's leaves (phi, b, the three scalars, both sublayers of all
# L + 1 blocks: what holds each path to the reference); of the LAST expert
# layer of the trunk ALL the held experts' three matrices, the router's and
# the router's BIAS; the prediction block's projection. In the reference at
# the cell's size the four wide blocks that rounding alone moves (`ln1`, both
# `phi`, `proj`: 0.02 to 0.06 of their norm a sound run, the same on every
# row) weigh 1 each; the experts' and the router's, which a token's flipped
# last choice moves too (0.15 and 0.26 of their norm, as steadily), weigh
# 0.45; the paths' biases and scalars, 144 and 18 numbers whose norm moves by
# half from row to row and whose error runs from 0.01 to 0.18, weigh 0.25
# (at 1 they were most of the spread of the whole's number between rows).
# The bias's block is zero on both sides (the bias is in the choice only)
# and anything but zero where the bias enters the weights, which nothing
# else tells from rounding: weighed to stand out. The blocks' norms in the
# reference and their errors: PERF.md section 6, PR 58, items 3 and 8
GRAD_SCALES = {"ln1": 180.0, "hc1_phi": 10.5, "hc2_phi": 3.15, "hc1_b": 310.0,
               "hc2_b": 110.0, "hc1_a": 520.0, "hc2_a": 130.0, "w_in": 7.0,
               "w_gate": 6.7, "w_out": 0.78, "router": 51.0,
               "router_bias": 4000.0, "proj": 15.0}


def flat_grads(grads) -> "Any":
    """The compared gradient: the blocks side by side, each times its
    constant, as ONE vector (benchmark/checks.py takes a norm of it)."""
    import jax.numpy as jnp

    return jnp.concatenate([
        (grads[name].astype(jnp.float32) * scale).reshape(-1)
        for name, scale in GRAD_SCALES.items()])


def nll_and_norm_grads(params, tokens, targets, spec, mode=None):
    """The reference's side: the MAIN head's per-position negative
    log-likelihood [T] and the compared gradient (`flat_grads`) of the whole
    loss, the prediction block's term in it."""
    nll, grads = ref.nll_and_grads(params, tokens, targets, spec, mode)
    return nll, flat_grads(grads)


# -- the program's side ------------------------------------------------------

STD = 0.02
# the sample a drawn router's bias is balanced on (trinity_afmoe.py says why
# rows of the cell's length): every output chosen about this often. 4 rows
# of 8192: the sample's streams are 4 x [4, 8192, 3584] float32, 1.9 GB
BALANCE_LOAD = 2048
BALANCE_SEQ = 8192
# H_res's bias leans to the identity by this much in the exponent
RES_LEAN = 2.0
# the static part of every coefficient is drawn this wide about its start
# (H_pre, H_post even): narrow, so that every seed's sublayers write into
# the streams at about the same weight. At 0.5 the sum of the FIRST FFN's
# H_post, whose output is most of the final stream, ran from 3.05 to 5.02
# of 4 over nine seeds, the later layers' share of the loss and of every
# gradient ran the other way, and the check's numbers with it (loss 0.024
# at 5.02, 0.052 at 3.05: chip, PR 58)
HC_BIAS_STD = 0.1
# the router's columns are drawn at unlike norms, these times 0.02,
# log-spaced in a seeded order: left alone a wide column's expert would take
# 3 x the even share and a narrow one's a fiftieth, so the balanced bias
# spreads over +-0.13 of a score (a router of like columns over a normed
# stream balances itself to +-0.014, and whether the bias enters the weight,
# control mode bias-in-weight, then moves nothing the check can see: chip,
# PR 58)
ROUTER_SPREAD = (0.5, 2.0)


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig: a share layer
    holds `n_routed_experts` of the `n_routed_experts_total` the router
    scores, from `held_experts_first` on; dropless is capacity_factor =
    held / selected."""
    from ray_tpu.models import StackConfig

    held, k = spec["n_routed_experts"], spec["num_experts_per_tok"]
    if spec.get("n_group", 1) != 1 or spec.get("topk_group", 1) != 1:
        raise ValueError("xing4_0: groups of experts other than n_group = "
                         "topk_group = 1 (the plain top k) are not written")
    s = spec["rope_scaling"]
    if s["type"] != "yarn":
        raise ValueError(f"xing4_0: rope_scaling {s['type']!r} is not written")
    fields = dict(
        name=spec["model_type"],
        vocab_size=spec["vocab_size"],
        d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        d_ff=spec["intermediate_size"],
        max_seq_len=spec["max_position_embeddings"],
        norm="rmsnorm", activation="swiglu", positional="none",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        num_experts=held, num_selected_experts=k,
        capacity_factor=max(held / k, 1.0), router_aux_coef=0.0,
        layer_kinds=("mla",) * spec["num_hidden_layers"],
        n_dense_layers=spec["first_k_dense_replace"],
        d_ff_expert=spec["moe_intermediate_size"],
        d_ff_shared=spec["n_shared_experts"] * spec["moe_intermediate_size"],
        router="sigmoid", norm_topk=bool(spec["norm_topk_prob"]),
        routed_scale=float(spec["routed_scaling_factor"]),
        n_routed_experts=spec["n_routed_experts_total"],
        experts_first=spec["held_experts_first"],
        router_bias_rate=float(spec["router_bias_update_rate"]),
        q_lora_rank=spec["q_lora_rank"], kv_lora_rank=spec["kv_lora_rank"],
        qk_nope_dim=spec["qk_nope_head_dim"],
        qk_rope_dim=spec["qk_rope_head_dim"], v_head_dim=spec["v_head_dim"],
        hc_streams=spec["hc_mult"],
        hc_sinkhorn_iters=spec["hc_sinkhorn_iters"],
        hc_eps=float(spec["hc_eps"]),
        hc_res_clamp=(float(spec["mhc_h_res_clamp_min"]),
                      float(spec["mhc_h_res_clamp_max"])),
        mtp_depth=spec["num_nextn_predict_layers"],
        mtp_weight=float(spec["mtp_loss_weight"]),
        rope_yarn=(float(s["factor"]),
                   int(s["original_max_position_embeddings"]),
                   float(s["beta_fast"]), float(s["beta_slow"]),
                   float(s["mscale"]), float(s["mscale_all_dim"])),
        dtype=spec["torch_dtype"],
    )
    fields.update(overrides)
    return StackConfig(**fields)


def balanced_layer(x, lp, spec: Dict[str, Any]):
    """One drawn layer `lp` over the sample's streams x [rows, n, T, D] ->
    (the streams after the layer, `lp` with its `router_bias` balanced on
    the sample where it holds a router): the plain reference's layer at the
    DEFAULT matmul precision, so that the held experts take near held /
    routed of the choices on every seed (the afmoe family's rule and its
    `balanced_bias`)."""
    import jax
    import jax.numpy as jnp

    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    if "router" in lp:
        eps = spec["rms_norm_eps"]

        def normed(one):  # what the router scores: the second half's input
            one = ref.residual(one, f32, "hc1", spec, None, lambda h: ref.mla(
                ref.rms_norm(h, f32["ln1"], eps), f32, spec))
            pre, _, _ = ref.mixing(one, f32, "hc2", spec)
            return ref.rms_norm(jnp.einsum("tj,jtd->td", pre, one),
                                f32["ln2"], eps)

        h = jax.lax.map(normed, x).reshape(-1, x.shape[-1])
        with jax.default_matmul_precision("highest"):
            score = jax.nn.sigmoid(h @ f32["router"])
        bias = afmoe.balanced_bias(score, spec["num_experts_per_tok"])
        # the one leaf that is NOT bf16: a buffer the train step moves by
        # 1e-3 a step, under bfloat16's resolution of a bias of 0.3
        lp = {**lp, "router_bias": bias}
        f32 = {**f32, "router_bias": bias}
    return jax.lax.map(lambda one: ref.layer(one, f32, spec), x), lp


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    a list of segments, each a tuple with one dict per layer of its period,
    stacked over repeats; `mtp` the prediction block), every leaf bf16 but
    the routers' biases (float32 buffers), drawn by the benchmark so that
    `correct` can SEE each mechanism: matrices normal(0.02), output
    projections 0.02 / sqrt(2 x the PUBLISHED depth), norm weights 1 +
    normal(0.02); the residual paths' phi normal(1 / sqrt(n D)), so a
    token's raw coefficients are about standard normal, under scalars a_*
    of 1 + normal(0.02): the dynamic term of each H spreads about +-1 over
    tokens; their bias normal(`HC_BIAS_STD`) about its start, H_res's
    leaning to the identity by `RES_LEAN`; the routers' columns at unlike
    norms (`ROUTER_SPREAD`) and their biases balanced on a sample of random
    tokens that passes through the layers as they are drawn
    (`balanced_layer`).
    Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    depth = spec.get("published", {}).get("num_hidden_layers", cfg.n_layers)
    out_std = STD / (2 * depth) ** 0.5
    n, D = cfg.hc_streams, cfg.d_model

    def draw(k, shape, init):
        normal = jax.random.normal(k, shape, jnp.float32)
        if init == "one":
            w = 1.0 + normal * STD
        elif init == "zero":
            w = jnp.zeros(shape, jnp.float32)
        elif init == "hc_a":
            w = 1.0 + normal * STD
        elif init == "hc_b":
            w = (HC_BIAS_STD * normal
                 + stack.hc_start(cfg, "hc_b", RES_LEAN))
        elif len(shape) == 3 and shape[:2] == (n, D) and n > 1:  # a phi
            w = normal / (n * D) ** 0.5
        else:
            w = normal * (out_std if init == "out" else STD)
        return w.astype(bf16)

    def layer(k, kind, half):
        shapes = stack.layer_shapes(cfg, kind, half)
        ks = jax.random.split(k, len(shapes))
        out = {name: draw(ks[i], *shapes[name])
               for i, name in enumerate(sorted(shapes))}
        if "router" in out:
            lo, hi = (math.log(r) for r in ROUTER_SPREAD)
            norms = jax.random.permutation(k, jnp.exp(jnp.linspace(
                lo, hi, out["router"].shape[-1])))
            out["router"] = (out["router"].astype(jnp.float32)
                             * norms).astype(bf16)
        return out

    k_emb, k_norm, k_head, k_layers, k_sample, k_mtp = jax.random.split(key, 6)
    V = cfg.vocab_size
    W, k = cfg.router_width, cfg.num_selected_experts
    embed = draw(k_emb, (V, D), "w")
    T = min(BALANCE_SEQ, cfg.max_seq_len)
    tokens = jax.random.randint(
        k_sample, (-(-BALANCE_LOAD * W // (k * T)), T), 0, V)
    first_sample = embed[tokens].astype(jnp.float32)
    sample = jnp.broadcast_to(first_sample[:, None],
                              (tokens.shape[0], n, T, D))
    segments = []
    for first, period, repeats in cfg.segments():
        ks = jax.random.split(jax.random.fold_in(k_layers, first),
                              repeats * len(period))
        ks = ks.reshape(repeats, len(period), *ks.shape[1:])

        def one_period(sample, ks, first=first, period=period):
            layers = []
            for i, kind in enumerate(period):
                # a layer is drawn once the one below is done with
                sample, ki = jax.lax.optimization_barrier((sample, ks[i]))
                sample, lp = balanced_layer(
                    sample, layer(ki, kind, cfg.second_halves[first + i]), spec)
                layers.append(lp)
            return sample, tuple(layers)

        sample, segment = jax.lax.scan(one_period, sample, ks)
        segments.append(segment)
    out = {"embed": embed, "layers": segments,
           "final_norm": draw(k_norm, (D,), "one"),
           "lm_head": draw(k_head, (D, V), "w")}
    if cfg.mtp_depth:
        ks = jax.random.split(k_mtp, 6)
        shapes = stack.mtp_shapes(cfg)
        mtp = {name: draw(ks[i], *shapes[name])
               for i, name in enumerate(sorted(shapes))}
        # the block's sample: the trunk's summed streams beside the NEXT
        # token's embedding, as a training row hands them over
        eps = spec["rms_norm_eps"]
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), mtp)
        g = jnp.concatenate([
            ref.rms_norm(jnp.sum(sample, axis=1), f32["h_norm"], eps),
            ref.rms_norm(jnp.roll(first_sample, -1, axis=1), f32["e_norm"],
                         eps)], axis=-1) @ f32["proj"]
        _, mtp["layer"] = balanced_layer(
            jnp.broadcast_to(g[:, None], sample.shape),
            layer(ks[5], cfg.layer_kinds[-1], "moe"), spec)
        out["mtp"] = mtp
    return out


def _order(layers):
    """(segment, place, repeat) of every layer of the tree in the model's
    order, and the LAST expert layer's."""
    import jax

    order = [(s, i, rep) for s, seg in enumerate(layers)
             for rep in range(jax.tree.leaves(seg)[0].shape[0])
             for i in range(len(seg))]
    last = max(m for m, (s, i, _) in enumerate(order)
               if "router" in layers[s][i])
    return order, order[last]


def program_probe(cfg, params, tokens, targets):
    """The program's own forward and backward (models.forward, the function
    the train step differentiates: latent attention's plain form through the
    flash kernels, the residual path, the grouped expert product, the
    prediction block, remat, bf16) on one row: the main head's per-position
    negative log-likelihood [T], and the compared gradient (`flat_grads`) of
    L = its mean + cfg.mtp_weight x the prediction block's loss."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import forward
    from ray_tpu.models.transformer import mtp_loss

    f32 = jnp.float32
    shared = ("ln1",) + ref.HC_LEAVES   # every block's compared leaves
    deep = ("router", "router_bias", *ref.EXPERT_LEAVES)

    def probe(params, tokens, targets):
        order, (ls, li, lrep) = _order(params["layers"])
        held, mp = params["layers"][ls][li], params["mtp"]
        probed = {
            "layers": [[{name: lp[name].astype(f32) for name in shared}
                        for lp in seg] for seg in params["layers"]],
            "mtp": {name: mp["layer"][name].astype(f32) for name in shared},
            "proj": mp["proj"].astype(f32),
            **{name: held[name][lrep].astype(f32) for name in deep}}

        def loss(probed):
            def put(lp, leaves):
                return {**lp, **{name: a.astype(lp[name].dtype)
                                 for name, a in leaves.items()}}

            layers = [[put(lp, leaves) for lp, leaves in zip(seg, segp)]
                      for seg, segp in zip(params["layers"], probed["layers"])]
            lp = layers[ls][li]
            layers[ls][li] = {**lp, **{
                name: lp[name].at[lrep].set(probed[name].astype(lp[name].dtype))
                for name in deep}}
            p = {**params, "layers": [tuple(seg) for seg in layers],
                 "mtp": {**mp, "layer": put(mp["layer"], probed["mtp"]),
                         "proj": probed["proj"].astype(mp["proj"].dtype)}}
            logits, _, after = forward(p, tokens[None], cfg,
                                       mtp_tokens=targets[None])
            lse = jax.scipy.special.logsumexp(logits[0], axis=-1)
            picked = jnp.take_along_axis(logits[0], targets[:, None], -1)[:, 0]
            nll = lse - picked
            return (jnp.mean(nll) + cfg.mtp_weight
                    * mtp_loss(after, targets[None], None)), nll

        (_, nll), g = jax.value_and_grad(loss, has_aux=True)(probed)
        grads = {name: g[name] for name in (*deep, "proj")}
        for name in shared:
            grads[name] = jnp.stack(
                [g["layers"][s][i][name][rep] for s, i, rep in order]
                + [g["mtp"][name]])
        return nll, flat_grads(grads)

    return jax.jit(probe)(params, tokens, targets)


# -- operations and bytes, from shapes ---------------------------------------


def _blocks(spec) -> int:
    """Layers a token passes: the trunk's and the prediction block."""
    return spec["num_hidden_layers"] + spec["num_nextn_predict_layers"]


def _mix_params(spec) -> int:
    """One sublayer's phi: the n D-wide token against n n + 2 n columns."""
    n = spec["hc_mult"]
    return n * spec["hidden_size"] * (n * n + 2 * n)


def layer_matmul_params(spec: Dict[str, Any]) -> Dict[str, float]:
    """Weights a token multiplies HERE: the attention's projections (the
    queries' and the latent's bottlenecks, the heads' up-projections, the
    rotary key's, the output's) and both sublayers' phi; a dense layer's
    FFN; an expert layer's router, shared expert and the held experts a
    token reaches on average (selected x held / routed); the head; the
    prediction block's projection."""
    D, H = spec["hidden_size"], spec["num_attention_heads"]
    N, R, V = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
               spec["v_head_dim"])
    ql, kl, Fe = (spec["q_lora_rank"], spec["kv_lora_rank"],
                  spec["moe_intermediate_size"])
    reached = (spec["num_experts_per_tok"] * spec["n_routed_experts"]
               / spec["n_routed_experts_total"])
    return {"attn": (D * ql + ql * H * (N + R) + D * (kl + R)
                     + kl * H * (N + V) + H * V * D + 2 * _mix_params(spec)),
            "dense": 3 * D * spec["intermediate_size"],
            "moe": (D * spec["n_routed_experts_total"]
                    + (spec["n_shared_experts"] + reached) * 3 * D * Fe),
            "head": D * spec["vocab_size"], "proj": 2 * D * D}


def train_flops_per_token(spec: Dict[str, Any], seq: int) -> float:
    """Forward + backward (2 x the forward) of one token in rows of `seq`,
    under benchmark/flops.py's conventions: the experts a token reaches
    HERE, the prediction block and its pass through the head, the scores at
    the heads' REAL widths (qk_nope + qk_rope against v_head_dim: the
    kernels' padding to 256 lanes is lost utilization, not work), no
    recomputation."""
    p = layer_matmul_params(spec)
    H = spec["num_attention_heads"]
    qk = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
    scores = 2 * H * (qk + spec["v_head_dim"]) * (seq + 1) / 2
    forward = 0.0
    for l in range(_blocks(spec)):
        half = "dense" if l < spec["first_k_dense_replace"] else "moe"
        forward += 2.0 * (p["attn"] + p[half]) + scores
    heads = 1 + spec["num_nextn_predict_layers"]
    forward += 2.0 * (heads * p["head"]
                      + spec["num_nextn_predict_layers"] * p["proj"])
    return 3 * forward


def _flash(spec, batch: int, seq: int, qk_products: int, v_products: int,
           qk_tensors: int, v_tensors: int) -> Dict[str, float]:
    H = spec["num_attention_heads"]
    qk = spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]
    V = spec["v_head_dim"]
    pairs = seq * (seq + 1) / 2
    return {"flops": batch * 2 * H * pairs * (qk_products * qk
                                              + v_products * V),
            "bytes": batch * seq * H * (qk_tensors * qk + v_tensors * V) * BF16}


def flash_forward(spec, batch: int, seq: int) -> Dict[str, float]:
    """One call of the causal flash forward over heads of qk_nope + qk_rope
    against values of v_head_dim: q k^T at the keys' width, p v at the
    values'; q and k once, v and o once."""
    return _flash(spec, batch, seq, 1, 1, 2, 2)


def flash_backward(spec, batch: int, seq: int) -> Dict[str, float]:
    """Its backward: s again, dq and dk at the keys' width; dp and dv at
    the values'; q, k, dq, dk and v, o, do, dv once each."""
    return _flash(spec, batch, seq, 3, 2, 4, 4)


def moe_grouped(spec, rows: float) -> Dict[str, float]:
    """The nine grouped products of ONE expert layer's step over `rows`
    rows that chose a held expert (trinity_afmoe.py `moe_grouped`)."""
    D, Fe, E = (spec["hidden_size"], spec["moe_intermediate_size"],
                spec["n_routed_experts"])
    return {"flops": 9 * 2 * rows * D * Fe,
            "bytes": 9 * (rows * (D + Fe) + E * D * Fe) * BF16}


def mhc(spec, batch: int, seq: int) -> Dict[str, float]:
    """The residual paths of ONE step over `batch` rows of `seq`, forward
    and backward, whatever implements them: a sublayer's forward reads the
    n streams once for the norm, the projection and the pre-mix, reads them
    again and writes them for the res- and post-mix, writes the sublayer's
    input and reads its output: (3 n + 2) rows of D a token; its backward
    as much again (the cotangents in the streams' place); recomputation
    counts nothing. Two sublayers a block, the prediction block among them."""
    n, D = spec["hc_mult"], spec["hidden_size"]
    sublayers = 2 * _blocks(spec)
    tokens = batch * seq
    return {"flops": tokens * sublayers * 3 * (
                2 * _mix_params(spec) + 2 * (n * n + 2 * n) * D),
            "bytes": tokens * sublayers * 2 * (3 * n + 2) * D * BF16}


work = {"flash_fwd": flash_forward, "flash_bwd": flash_backward,
        "moe_grouped": moe_grouped, "mhc": mhc}


def held_experts(spec: Dict[str, Any]) -> int:
    return spec["n_routed_experts"]


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    if group.startswith("flash"):
        return _blocks(spec)
    return _blocks(spec) - spec["first_k_dense_replace"]


# -- the CPU's cut -----------------------------------------------------------

# float32 sums, as the trinity family's cut and for its reason: at rows of
# 128 tokens a bfloat16 flip of a token's last choice is most of an expert's
# gradient block; in float32 the rehearsal shows the control flow under the
# cell's OWN limits
SHRINK = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=128,
              num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              n_routed_experts=4, n_routed_experts_total=8,
              num_experts_per_tok=2, vocab_size=512,
              max_position_embeddings=512, torch_dtype="float32")
SHRINK_ROPE = dict(factor=8, original_max_position_embeddings=32)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    return {**spec, **SHRINK,
            "rope_scaling": {**spec["rope_scaling"], **SHRINK_ROPE}}
