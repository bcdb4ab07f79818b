"""The family of `solar-open2-250b` (upstage/Solar-Open2-250B, `model_type`
solar_open2): three layers of Kimi Delta Attention (a delta rule whose decay
is a vector over the key channels: a float32 [128, 128] state matrix per
head and sequence, after a short convolution over q, k and v) to one of GQA
with no positional encoding whose heads' outputs are gated, the GQA layer
FIRST of its period; every layer's second half a share of 320 small experts
(8 a token by sigmoid score + bias, renormalised over all eight) beside one
shared expert; RMSNorm before each sublayer, SwiGLU, an untied head. Its
plain reference is benchmark/reference/solar_open2.py, which holds every
equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves."""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.families import window_full_moe as gqa
from benchmark.reference import solar_open2 as ref

# -- the plain reference -----------------------------------------------------

# few lengths: a reference pass compiles its mixers a length (13 s of the
# TPU compiler's time), and the check replays prompts of 32 to 14 k tokens:
# the short class passes at 8192, the long one at 8192 or 16384 (the experts
# take the real tokens alone: `ref.hidden_states`)
PAD_TO = 32 * ref.Q_BLOCK
logits_at = ref.logits_at
# int8 / fp8: every matmul weight rounded; state-bf16: the delta-rule state
# kept in bfloat16; router-bf16: the router's scores in bfloat16 (the last
# two reported without a limit: the program's are float32)
modes = ("int8", "fp8", "state-bf16", "router-bf16")

F32 = 4


def kinds(spec: Dict[str, Any]):
    return tuple(ref.kind_of(l, spec)
                 for l in range(spec["num_hidden_layers"]))


def state_dims(spec: Dict[str, Any]):
    """(heads, key size, value size) of a linear layer's state."""
    linear = spec["linear_attn_config"]
    if linear["num_kv_heads"] not in (None, linear["num_heads"]):
        raise ValueError("key heads shared by several value heads are not "
                         "written down here: the published model has one "
                         "key head a value head (`num_kv_heads` null)")
    return linear["num_heads"], linear["head_dim"], linear["head_dim"]


def low_rank(spec: Dict[str, Any]) -> int:
    """The width of the decay's and the output gate's low-rank pairs: the
    linear head size (Kimi Linear's; `assumed` in the configuration's
    file)."""
    return spec["linear_attn_config"]["head_dim"]


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig: "attn" where
    `gqa_layers` says and "gdn" elsewhere, the decay a key channel's through
    the low-rank pair, the share of the experts this chip holds
    (`n_routed_experts` of `n_routed_experts_total`, from
    `held_experts_first`), the shared experts as ONE gated FFN. Dropless
    routing is capacity_factor = held experts / selected."""
    from ray_tpu.models import StackConfig

    for key, only in (("use_rope", False), ("kda_use_full_proj", False),
                      ("n_group", 1), ("topk_group", 1)):
        if spec.get(key, only) != only:
            raise ValueError(f"{key} {spec[key]!r}: this family is written "
                             f"for {only!r}")
    heads, dk, dv = state_dims(spec)
    held, selected = spec["n_routed_experts"], spec["num_experts_per_tok"]
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
        norm="rmsnorm", activation="swiglu", positional="none",
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec.get("torch_dtype", "bfloat16"),
        layer_kinds=kinds(spec),
        conv_taps=spec["linear_attn_config"]["short_conv_kernel_size"],
        gdn_heads=heads, gdn_key_dim=dk, gdn_value_dim=dv,
        gdn_neg_eigval=bool(spec["kda_allow_neg_eigval"]),
        gdn_channel_rank=low_rank(spec), gdn_gate_rank=low_rank(spec),
        attn_gate=bool(spec["use_gqa_gate"]),
        num_experts=held, num_selected_experts=selected,
        capacity_factor=held / min(selected, held), router_aux_coef=0.0,
        n_routed_experts=spec["n_routed_experts_total"],
        experts_first=spec["held_experts_first"],
        n_dense_layers=spec["first_k_dense_replace"],
        d_ff_expert=spec["moe_intermediate_size"],
        d_ff_shared=spec["n_shared_experts"] * spec["moe_intermediate_size"],
        router="sigmoid", norm_topk=bool(spec["norm_topk_prob"]),
        routed_scale=float(spec["routed_scaling_factor"]),
    )
    fields.update(overrides)
    return StackConfig(**fields)


STD = 0.02
TAP_STD = 0.5
# the sample the router's bias is balanced on: sequences of BALANCE_SEQ
# random tokens, as many as give every one of the router's outputs about
# BALANCE_LOAD choices (its load then reads within 1 / sqrt(BALANCE_LOAD) on
# other tokens, and the 20 held experts' sum within a quarter of that)
BALANCE_LOAD = 400
BALANCE_SEQ = 2048
BALANCE_STEPS = 200


def balanced_bias(score, k: int):
    """score [N, E] (the router's sigmoid scores of a sample of tokens) ->
    the bias [E], mean 0, under which the k largest of score + bias fall on
    every output equally often: the auxiliary-loss-free balancing of the
    DeepSeek-V3 line (arXiv:2408.15664), whose buffer this bias is, run to
    its fixed point on one batch instead of along a training run: an output
    chosen too often has its bias lowered in proportion, in steps that
    shrink from a fifth of the scores' spread to a hundredth of that."""
    import jax
    import jax.numpy as jnp

    N, E = score.shape
    target = N * k / E

    def step(i, bias):
        _, ids = jax.lax.top_k(score + bias, k)
        # counted by comparison: a scatter of N k single additions is
        # serial on the TPU
        load = jnp.sum((ids[..., None] == jnp.arange(E)).astype(jnp.float32),
                       axis=(0, 1))
        rate = 0.05 * 0.01 ** (i / (BALANCE_STEPS - 1.0))
        return bias - rate * jnp.clip(load / target - 1.0, -1.0, 1.0)

    bias = jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((E,), jnp.float32))
    return bias - jnp.mean(bias)


def balanced_layer(x, lp, kind: str, spec: Dict[str, Any]):
    """One drawn layer `lp` over the sample's stream x [n, T, D] -> (the
    stream after the layer, `lp` with its `router_bias` set so that the
    layer's choices fall evenly over ALL the router's outputs on the
    sample): the plain reference's mixer a sequence at a time, the bias
    solved on the router's scores of every token, the reference's experts
    under that bias; its matrix products at the DEFAULT precision, the
    served model's own (a balance needs no float32 stream, and the TPU's
    compiler takes 26 s longer over the `highest` ones: my compile for a
    described v5e, PR 52). Why: a stream of random weights is not isotropic (the
    linear layers' normed, SiLU-fed outputs share a direction), so a random
    router prefers a few outputs by a wide margin (single outputs at 0 to
    22 x the even share), and WHICH is the seed's draw: the share of a
    token's eight choices that fall on the 20 held here then reads 5.4 to
    6.9% from seed to seed (my chip runs, PR 52) where a trained router's,
    whose bias is balanced for this, reads 20 / 320."""
    import jax
    import jax.numpy as jnp

    items = ref.static(spec)
    x, b = jax.lax.map(
        lambda one: ref._mix(one, lp, kind=kind, items=items, mode=None,
                             precision="default"), x)
    b = b.reshape(-1, b.shape[-1])
    # the scores alone at `highest`: they decide which outputs are chosen
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(b @ lp["router"].astype(jnp.float32))
    bias = balanced_bias(score, spec["num_experts_per_tok"])
    lp = {**lp, "router_bias": bias.astype(lp["router_bias"].dtype)}
    half = {n: lp[n] for n in ref.SECOND_HALF}
    return x + ref._experts(b, half, items=items, mode=None,
                            precision="default").reshape(x.shape), lp


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16, drawn by the benchmark:
    matrices normal(0.02), output projections 0.02 / sqrt(2 x the PUBLISHED
    depth), norm weights 1 + normal(0.02), the convolution's taps
    normal(0.5), the router normal(0.02), the output gate's bias zero; the
    decay as Kimi Linear initialises it: A uniform in (1, 16) a head
    (`d_A_log` its logarithm), `d_dt_b` a lane the inverse softplus of a
    log-uniform step in [0.001, 0.1]; the router's bias balanced, as a
    trained router's is, on a sample of random tokens that passes through
    the layers as they are drawn (`balanced_layer`). Traceable: call under
    jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    depth = spec.get("published", {}).get("num_hidden_layers", cfg.n_layers)
    out_std = STD / (2 * depth) ** 0.5

    def draw(k, name, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "d_A_log":
            w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "d_dt_b":
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            w = jnp.log(jnp.expm1(step))
        elif name == "d_conv":
            w = n * TAP_STD
        elif init == "one":
            w = 1.0 + n * STD
        elif init == "zero":
            w = jnp.zeros(shape, jnp.float32)
        else:
            w = n * (out_std if init == "out" else STD)
        return w.astype(bf16)

    def layer(k, kind, half):
        shapes = stack.layer_shapes(cfg, kind, half)
        ks = jax.random.split(k, len(shapes))
        return {name: draw(ks[i], name, *shapes[name])
                for i, name in enumerate(sorted(shapes))}

    k_emb, k_norm, k_head, k_layers, k_sample = jax.random.split(key, 5)
    D, V = cfg.d_model, cfg.vocab_size
    E, k = spec["n_routed_experts_total"], spec["num_experts_per_tok"]
    embed = draw(k_emb, "embed", (V, D), "w")
    T = min(BALANCE_SEQ, cfg.max_seq_len)
    tokens = jax.random.randint(
        k_sample, (-(-BALANCE_LOAD * E // (k * T)), T), 0, V)
    sample = embed[tokens].astype(jnp.float32)
    segments = []
    for first, period, repeats in cfg.segments():
        ks = jax.random.split(jax.random.fold_in(k_layers, first),
                              repeats * len(period))
        ks = ks.reshape(repeats, len(period), *ks.shape[1:])

        def one_period(sample, ks, first=first, period=period):
            """A period's layers drawn and balanced in the model's order,
            one period an iteration: the float32 draws of a stacked
            segment of experts, or every layer's slice of it beside the
            sample, would be gigabytes of temporaries."""
            layers = []
            for i, kind in enumerate(period):
                # a layer is drawn once the one below is done with
                sample, ki = jax.lax.optimization_barrier((sample, ks[i]))
                sample, lp = balanced_layer(
                    sample, layer(ki, kind, cfg.second_halves[first + i]),
                    kind, spec)
                layers.append(lp)
            return sample, tuple(layers)

        sample, segment = jax.lax.scan(one_period, sample, ks)
        segments.append(segment)
    return {"embed": embed,
            "layers": segments,
            "final_norm": draw(k_norm, "final_norm", (D,), "one"),
            "lm_head": draw(k_head, "lm_head", (D, V), "w")}


# -- operations and bytes, from the equations --------------------------------


# the GQA layers run the accepted paged kernels over one pool: their counts
# are the window-and-full family's, from this configuration's own keys
# (`num_attention_heads`, `num_key_value_heads`, `head_dim`), no window
paged_decode, paged_chunk = gqa.paged_decode, gqa.paged_chunk


def _recurrence(spec, tokens: float) -> Dict[str, float]:
    """The channel-decay delta rule over `tokens` (position, sequence)
    pairs of one layer, from its equations: per head, token and state
    element one product for the decay (a row's own factor where the scalar
    form has the block's), a product and a sum each for S'^T k, for the
    rank-one correction and for S^T q (7 dk dv), and one exponential a key
    lane for the decay (dk); q, k, v, the decay's dk lanes and beta read
    and o written, in float32. What a kernel's METHOD spends beside that
    (the WY form's triangular solve, the channel form's pairwise diagonal
    sub-blocks) is no part of what the algorithm needs
    (benchmark/flops.py), and counting it would raise the share of a
    kernel that works more."""
    H, dk, dv = state_dims(spec)
    return {"flops": H * (7 * dk * dv + dk) * tokens,
            "bytes": H * (3 * dk + 2 * dv + 1) * F32 * tokens}


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
    written once. An empty slot counts nothing."""
    H, dk, dv = state_dims(spec)
    work = _recurrence(spec, slots)
    work["bytes"] += 2 * H * dk * dv * F32 * slots
    return work


work = {"paged_decode": paged_decode, "paged_chunk": paged_chunk,
        "gdn_chunk": gdn_chunk, "gdn_step": gdn_step}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step, one chunk): the GQA
    layers attend and hold a cache, the linear layers run the recurrence."""
    k = kinds(spec)
    return {"paged_decode": k.count("attn"), "paged_chunk": k.count("attn"),
            "gdn_chunk": k.count("gdn"), "gdn_step": k.count("gdn")}[group]


def chunk_attention_work(spec: Dict[str, Any], start: int,
                         tokens: int) -> Dict[str, float]:
    """Operations and bytes of ONE chunk program's attention calls, every
    GQA layer's: `tokens` real tokens from position `start`, row c scoring
    the start + c + 1 keys up to its own, every key read once."""
    seen = gqa.chunk_keys(spec, start, tokens, window=False)
    one = paged_chunk(spec, seen["reads"], seen["pairs"])
    return {k: calls_per_pass(spec, "paged_chunk") * v for k, v in one.items()}


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
              num_hidden_layers=8, gqa_layers=[0, 4], num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, n_routed_experts=4,
              n_routed_experts_total=16, num_experts_per_tok=3,
              vocab_size=256, max_position_embeddings=512)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Two whole periods of the layer pattern: 4 linear heads of 8 x 8, 4
    of 16 experts held, top 3, beside the shared expert."""
    return {**spec, **SHRINK,
            "linear_attn_config": {**spec["linear_attn_config"],
                                   "head_dim": 8, "num_heads": 4}}
