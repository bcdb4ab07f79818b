"""The family of `trinity-mini` (arcee-ai/Trinity-Mini, `model_type` afmoe):
gated GQA whose queries and keys are normalised per head, three rotary
layers over a window and then one over every key with no positions; a norm
on BOTH sides of every sublayer; leading dense layers, then a share of many
small experts chosen by sigmoid score + bias, renormalised and scaled, beside
one shared expert; the embedding times sqrt(hidden_size). The family's cells
TRAIN it: one chip's share of each layer (the held experts, an eighth of the
vocabulary). Its plain reference is benchmark/reference/trinity.py.

What a family file holds: benchmark/families/mistral.py states the contract.
(The file is not `afmoe.py`, the name ISSUE 54 gave it: the benchmark's own
test of an unknown family expects `mistral.py` FIRST in the sorted list of
this directory, and that test's file is not this PR's to edit.)
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import trinity as ref

# -- the plain reference -----------------------------------------------------

PAD_TO = ref.Q_BLOCK
logits_at = ref.logits_at
modes = ref.EQUATION_MODES + ref.PRECISION_MODES
# what each block of the compared gradient is multiplied by, on both sides,
# so that none is under a tenth of the whole's norm in the reference: the
# first norms' weights [L, D], and of the LAST expert layer ALL the held
# experts' three matrices and the router's matrix (the leaves only the
# grouped product's backward reaches). All the held experts and not one:
# under a random router ONE expert's rows, and with them its gradient's
# norm, read 0.3 to 3 x the even share from seed to seed, and moved the
# whole number with them; the held experts together read 0.9 to 1.15 x. The
# blocks' norms in the reference at the cell's size: PERF.md section 6, PR 54
GRAD_SCALES = {"ln1": 1.0, "w_in": 0.06, "w_gate": 0.06, "w_out": 0.0075,
               "router": 0.6}


def flat_grads(grads) -> "Any":
    """The compared gradient: the blocks side by side, each times its
    constant, as ONE vector (benchmark/checks.py takes a norm of it)."""
    import jax.numpy as jnp

    return jnp.concatenate([
        (grads[name].astype(jnp.float32) * scale).reshape(-1)
        for name, scale in GRAD_SCALES.items()])


def nll_and_norm_grads(params, tokens, targets, spec, mode=None):
    """The reference's side: per-position negative log-likelihood [T] and
    the compared gradient (`flat_grads`): every layer's first norm weight
    and, of the last expert layer, the held experts' three matrices and the
    router's, which only the grouped product's backward reaches."""
    nll, grads = ref.nll_and_grads(params, tokens, targets, spec, mode)
    return nll, flat_grads(grads)


# -- the program's side ------------------------------------------------------

STD = 0.02
# the sample a drawn router's bias is balanced on: tokens enough for every
# output to be chosen this often, in rows of the length the cell trains on:
# what a layer hands the router depends on how many keys a position's
# attention averaged (the norm after the sublayer rescales whatever is left),
# and a bias balanced on rows of 2048 left single outputs at 0.1 to 3 x the
# even share on rows of 8192 (my chip run, PR 54)
BALANCE_LOAD = 8192
BALANCE_SEQ = 8192
BALANCE_STEPS = 50


def kinds(spec: Dict[str, Any]):
    return tuple("swa" if t == "sliding_attention" else "attn"
                 for t in spec["layer_types"])


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig: a share layer
    holds `num_experts` of the `num_experts_routed` the router scores, from
    `experts_first` on; dropless is capacity_factor = held / selected."""
    from ray_tpu.models import StackConfig

    held, k = spec["num_experts"], spec["num_experts_per_tok"]
    if spec.get("n_group", 1) != 1 or spec.get("topk_group", 1) != 1:
        raise ValueError("afmoe: groups of experts other than n_group = "
                         "topk_group = 1 (the plain top k) are not written")
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
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        num_experts=held, num_selected_experts=k,
        capacity_factor=max(held / k, 1.0), router_aux_coef=0.0,
        layer_kinds=kinds(spec), window=spec["sliding_window"],
        qk_norm=True, attn_gate=True, norm_place="both",
        n_dense_layers=spec["num_dense_layers"],
        d_ff_expert=spec["moe_intermediate_size"],
        d_ff_shared=spec["num_shared_experts"] * spec["moe_intermediate_size"],
        router="sigmoid", norm_topk=bool(spec["route_norm"]),
        routed_scale=float(spec["route_scale"]),
        n_routed_experts=spec["num_experts_routed"],
        experts_first=spec["experts_first"],
        router_bias_rate=float(spec["load_balance_coeff"]),
        embedding_multiplier=(spec["hidden_size"] ** 0.5
                              if spec["mup_enabled"] else 1.0),
        dtype=spec["torch_dtype"],
    )
    fields.update(overrides)
    return StackConfig(**fields)


def balanced_bias(score, k: int):
    """score [N, W] (the router's sigmoid scores of a sample of tokens) ->
    the bias [W], mean 0, under which the k largest of score + bias fall on
    every output equally often: the balancing the bias is trained by
    (arXiv:2408.15664), run to its fixed point on one batch instead of along
    a training run (the rule of benchmark/families/solar_open2.py)."""
    import jax
    import jax.numpy as jnp

    N, W = score.shape
    target = N * k / W

    def step(i, bias):
        _, ids = jax.lax.top_k(score + bias, k)
        load = jnp.sum((ids[..., None] == jnp.arange(W)).astype(jnp.float32),
                       axis=(0, 1))
        rate = 0.05 * 0.01 ** (i / (BALANCE_STEPS - 1.0))
        return bias - rate * jnp.clip(load / target - 1.0, -1.0, 1.0)

    bias = jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((W,), jnp.float32))
    return bias - jnp.mean(bias)


def balanced_layer(x, lp, kind: str, spec: Dict[str, Any]):
    """One drawn layer `lp` over the sample's stream x [n, T, D] -> (the
    stream after the layer, `lp` with its `router_bias` balanced on the
    sample where it holds a router): the plain reference's layer at the
    DEFAULT matmul precision. A random router over a stream of random
    weights prefers a few outputs by a wide margin, and WHICH is the seed's
    draw; a trained one's bias is balanced for exactly this. The held
    experts then take near held / routed of the choices on every seed."""
    import jax
    import jax.numpy as jnp

    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    if "router" in lp:
        eps = spec["rms_norm_eps"]

        def normed(one):  # the stream the router scores: after the mixer
            a = ref.gqa(ref.rms_norm(one, f32["ln1"], eps), f32, spec, kind)
            return ref.rms_norm(
                one + ref.rms_norm(a, f32["ln1_post"], eps), f32["ln2"], eps)

        h = jax.lax.map(normed, x).reshape(-1, x.shape[-1])
        with jax.default_matmul_precision("highest"):
            score = jax.nn.sigmoid(h @ f32["router"])
        bias = balanced_bias(score, spec["num_experts_per_tok"])
        # the one leaf that is NOT bf16: a buffer the train step moves by
        # 1e-3 a step, under bfloat16's resolution of a bias of 0.3
        lp = {**lp, "router_bias": bias}
        f32 = {**f32, "router_bias": bias}
    return jax.lax.map(lambda one: ref.layer(one, f32, kind, spec), x), lp


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16 but the router's bias
    (float32, a buffer), drawn by the benchmark:
    matrices normal(0.02), output projections 0.02 / sqrt(2 x the PUBLISHED
    depth), norm weights 1 + normal(0.02), the router normal(0.02) (its
    scores of a normed stream then spread over 0.3 .. 0.7, so the chosen
    eight carry unlike weights), the router's bias balanced on a sample of
    random tokens that passes through the layers as they are drawn
    (`balanced_layer`). Traceable: call under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    depth = spec.get("published", {}).get("num_hidden_layers", cfg.n_layers)
    out_std = STD / (2 * depth) ** 0.5

    def draw(k, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if init == "one":
            w = 1.0 + n * STD
        elif init == "zero":
            w = jnp.zeros(shape, jnp.float32)
        else:
            w = n * (out_std if init == "out" else STD)
        return w.astype(bf16)

    def layer(k, kind, half):
        shapes = stack.layer_shapes(cfg, kind, half)
        ks = jax.random.split(k, len(shapes))
        return {name: draw(ks[i], *shapes[name])
                for i, name in enumerate(sorted(shapes))}

    k_emb, k_norm, k_head, k_layers, k_sample = jax.random.split(key, 5)
    D, V = cfg.d_model, cfg.vocab_size
    W, k = cfg.router_width, cfg.num_selected_experts
    embed = draw(k_emb, (V, D), "w")
    T = min(BALANCE_SEQ, cfg.max_seq_len)
    tokens = jax.random.randint(
        k_sample, (-(-BALANCE_LOAD * W // (k * T)), T), 0, V)
    sample = embed[tokens].astype(jnp.float32) * cfg.embedding_multiplier
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
                    sample, layer(ki, kind, cfg.second_halves[first + i]),
                    kind, spec)
                layers.append(lp)
            return sample, tuple(layers)

        sample, segment = jax.lax.scan(one_period, sample, ks)
        segments.append(segment)
    return {"embed": embed,
            "layers": segments,
            "final_norm": draw(k_norm, (D,), "one"),
            "lm_head": draw(k_head, (D, V), "w")}


def program_probe(cfg, params, tokens, targets):
    """The program's own forward and backward (models.forward, the function
    the train step differentiates: the flash kernels with and without a
    window, the grouped expert product, remat, bf16) on one row:
    per-position negative log-likelihood [T], and the compared gradient
    (`flat_grads`) of its mean."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import forward

    f32 = jnp.float32

    def spots(layers):
        """(segment, place) of every layer in order; the last expert's."""
        order = [(s, i, rep) for s, seg in enumerate(layers)
                 for rep in range(jax.tree.leaves(seg)[0].shape[0])
                 for i in range(len(seg))]
        last = max(n for n, (s, i, _) in enumerate(order)
                   if "router" in layers[s][i])
        return order, order[last]

    def probe(params, tokens, targets):
        order, (ls, li, lrep) = spots(params["layers"])
        held = params["layers"][ls][li]
        probed = {"ln1": [[lp["ln1"].astype(f32) for lp in seg]
                          for seg in params["layers"]],
                  **{n: held[n][lrep].astype(f32)
                     for n in ("router", *ref.EXPERT_LEAVES)}}

        def mean_nll(probed):
            layers = [[{**lp, "ln1": ln1.astype(lp["ln1"].dtype)}
                       for lp, ln1 in zip(seg, ln1s)]
                      for seg, ln1s in zip(params["layers"], probed["ln1"])]
            lp = layers[ls][li]
            layers[ls][li] = {**lp, **{
                n: lp[n].at[lrep].set(probed[n].astype(lp[n].dtype))
                for n in ("router", *ref.EXPERT_LEAVES)}}
            p = {**params, "layers": [tuple(seg) for seg in layers]}
            logits, _ = forward(p, tokens[None], cfg)
            lse = jax.scipy.special.logsumexp(logits[0], axis=-1)
            picked = jnp.take_along_axis(logits[0], targets[:, None], -1)[:, 0]
            nll = lse - picked
            return jnp.mean(nll), nll

        (_, nll), g = jax.value_and_grad(mean_nll, has_aux=True)(probed)
        g["ln1"] = jnp.stack([g["ln1"][s][i][rep] for s, i, rep in order])
        return nll, flat_grads(g)

    return jax.jit(probe)(params, tokens, targets)


# -- operations and bytes, from shapes ---------------------------------------


def window_pairs(seq: int, window: int) -> float:
    """(query, key) pairs of one row of `seq` tokens inside a window."""
    w = min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def layer_matmul_params(spec: Dict[str, Any]) -> Dict[str, float]:
    """Weights a token multiplies HERE: the attention's five projections;
    a dense layer's FFN; an expert layer's router, shared expert and the
    held experts a token reaches on average (selected x held / routed)."""
    D, hd = spec["hidden_size"], spec["head_dim"]
    H, KVH = spec["num_attention_heads"], spec["num_key_value_heads"]
    Fe = spec["moe_intermediate_size"]
    reached = (spec["num_experts_per_tok"] * spec["num_experts"]
               / spec["num_experts_routed"])
    return {"attn": D * hd * (3 * H + 2 * KVH),
            "dense": 3 * D * spec["intermediate_size"],
            "moe": (D * spec["num_experts_routed"]
                    + (spec["num_shared_experts"] + reached) * 3 * D * Fe),
            "head": D * spec["vocab_size"]}


def train_flops_per_token(spec: Dict[str, Any], seq: int) -> float:
    """Forward + backward (2 x the forward) of one token in rows of `seq`,
    under benchmark/flops.py's conventions: the experts a token reaches
    HERE, window layers by the pairs inside the window, no recomputation."""
    p = layer_matmul_params(spec)
    H, hd = spec["num_attention_heads"], spec["head_dim"]
    forward = 2.0 * p["head"]
    for l, kind in enumerate(kinds(spec)):
        pairs = (window_pairs(seq, spec["sliding_window"]) if kind == "swa"
                 else seq * (seq + 1) / 2)
        half = "dense" if l < spec["num_dense_layers"] else "moe"
        forward += 2.0 * (p["attn"] + p[half]) + 2 * 2 * H * hd * pairs / seq
    return 3 * forward


def _flash(spec, batch: int, pairs: float, seq: int, products: int,
           tensors: int) -> Dict[str, float]:
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    return {"flops": batch * products * 2 * H * hd * pairs,
            "bytes": batch * seq * hd * tensors * (H + KVH) * BF16}


def flash_forward(spec, batch: int, seq: int) -> Dict[str, float]:
    """One call of the causal flash forward (the ONE full layer): two
    products a pair; q, o at H heads and k, v at KVH, each once."""
    return _flash(spec, batch, seq * (seq + 1) / 2, seq, 2, 2)


def flash_backward(spec, batch: int, seq: int) -> Dict[str, float]:
    """Its backward (dq, dk/dv kernels together): five products a pair."""
    return _flash(spec, batch, seq * (seq + 1) / 2, seq, 5, 4)


def flash_window_forward(spec, batch: int, seq: int) -> Dict[str, float]:
    """One call of the window forward: the pairs inside the window; the
    bytes of q and o once and of the key and value blocks a window visits
    (a query block's span of blocks, over the rows: about window / block + 1
    blocks of 1024 a block of queries)."""
    out = _flash(spec, batch, window_pairs(seq, spec["sliding_window"]),
                 seq, 2, 2)
    return {**out, "bytes": out["bytes"] + _window_rereads(spec, batch, seq)}


def flash_window_backward(spec, batch: int, seq: int) -> Dict[str, float]:
    out = _flash(spec, batch, window_pairs(seq, spec["sliding_window"]),
                 seq, 5, 4)
    return {**out, "bytes": out["bytes"] + 2 * _window_rereads(spec, batch, seq)}


def _window_rereads(spec, batch: int, seq: int, block: int = 1024) -> float:
    """Bytes of the key and value blocks a window's grid reads beyond one
    pass: every query HEAD's block of `block` rows reads its span of key
    blocks (the kernels' GQA index map names a KV head's block once a query
    head)."""
    H, hd = spec["num_attention_heads"], spec["head_dim"]
    span = min(spec["sliding_window"] // block + 1, seq // block)
    return batch * H * (seq // block) * max(span - 1, 0) * block * hd * 2 * BF16


def moe_grouped(spec, rows: float) -> Dict[str, float]:
    """The nine grouped products of ONE expert layer's step over `rows`
    rows that chose a held expert (three forward, three for the rows'
    gradient, three for the weights'): 2 x rows x D x F operations each;
    each reads or writes the rows once at D and once at F, and the held
    experts' matrix once."""
    D, Fe, E = (spec["hidden_size"], spec["moe_intermediate_size"],
                spec["num_experts"])
    return {"flops": 9 * 2 * rows * D * Fe,
            "bytes": 9 * (rows * (D + Fe) + E * D * Fe) * BF16}


work = {"flash_fwd": flash_forward, "flash_bwd": flash_backward,
        "flash_window_fwd": flash_window_forward,
        "flash_window_bwd": flash_window_backward,
        "moe_grouped": moe_grouped}


def held_experts(spec: Dict[str, Any]) -> int:
    return spec["num_experts"]


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    if group.startswith("flash_window"):
        return kinds(spec).count("swa")
    if group.startswith("flash"):
        return kinds(spec).count("attn")
    return spec["num_hidden_layers"] - spec["num_dense_layers"]


# -- the CPU's cut -----------------------------------------------------------

# float32 sums: the tiny cut's rows are 128 tokens, a handful of rows an
# expert, where bfloat16 flips of a token's last choice are most of the
# experts' gradient blocks (0.15 against the cell's limit of 0.1); in float32
# the rehearsal shows the program's control flow under the cell's OWN limits
SHRINK = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=128,
              num_attention_heads=4, num_key_value_heads=2, head_dim=32,
              num_experts=4, num_experts_routed=8, num_experts_per_tok=2,
              vocab_size=512, max_position_embeddings=512, sliding_window=16,
              torch_dtype="float32")


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    return {**spec, **SHRINK}
