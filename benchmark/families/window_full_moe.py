"""The family of `smallthinker-21b-a3b` (PowerInfer/SmallThinker-21BA3B-
Instruct): ordinary GQA layers of two kinds in one stack, one of full
attention that encodes no position and then three rotary layers over a
window, each layer caching its OWN keys (two page spaces of one row shape,
which one allocator serves); in every layer 64 small ReGLU experts, 6 a
token by a softmax over the chosen, scored from the layer's input stream
before the attention runs; untied head. Its plain reference is
benchmark/reference/smallthinker.py, which holds every equation.

What a family file holds is stated at the head of
benchmark/families/mistral.py. This family only serves."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.flops import BF16
from benchmark.reference import smallthinker as ref

# -- the plain reference -----------------------------------------------------

# whole blocks of queries, and few lengths: a reference pass compiles a
# program a length, and the check replays prompts of 32 to 14 k tokens
PAD_TO = 8 * ref.Q_BLOCK
logits_at = ref.logits_at
# every matmul weight rounded; the router in bfloat16; the two readings the
# configuration's `assumed` rules out (the program must fail both)
modes = ("int8", "fp8", "router-bf16", "router-after-norm", "silu")


def kinds(spec: Dict[str, Any]):
    """The program's kind of each layer, from the two published layouts: a
    layer with a window is rotary and a layer without encodes nothing."""
    if spec["rope_layout"] != spec["sliding_window_layout"]:
        raise ValueError("a windowed layer without the rotary turn, or a "
                         "rotary layer of full attention, is not written")
    return tuple("swa" if w else "attn" for w in
                 spec["sliding_window_layout"][:spec["num_hidden_layers"]])


# -- the program's side ------------------------------------------------------


def model_config(spec: Dict[str, Any], **overrides: Any):
    """The configuration's keys to the program's StackConfig. Dropless
    routing is capacity_factor = experts / selected."""
    from ray_tpu.models import StackConfig

    experts = spec["moe_num_primary_experts"]
    selected = spec["moe_num_active_primary_experts"]
    if not (spec["moe_primary_router_apply_softmax"]
            and spec["norm_topk_prob"]):
        raise ValueError("the router is written as a softmax over the "
                         "chosen experts' logits")
    fields = dict(
        name=spec["model_name"],
        vocab_size=spec["vocab_size"],
        d_model=spec["hidden_size"],
        n_layers=spec["num_hidden_layers"],
        n_heads=spec["num_attention_heads"],
        n_kv_heads=spec["num_key_value_heads"],
        head_dim=spec["head_dim"],
        d_ff=spec["moe_ffn_hidden_size"],
        max_seq_len=spec["max_position_embeddings"],
        norm="rmsnorm", activation="reglu", positional="none",
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec.get("torch_dtype", "bfloat16"),
        num_experts=experts, num_selected_experts=selected,
        capacity_factor=experts / selected, router_aux_coef=0.0,
        layer_kinds=kinds(spec), window=spec["sliding_window_size"],
        router="softmax", router_input="layer",
    )
    fields.update(overrides)
    return StackConfig(**fields)


STD = 0.02
# the router's logits spread about 1.5 around 0 (a unit-RMS stream of width
# D against normal(ROUTER_STD)): the chosen six then carry unlike weights,
# as a trained router's do, and not a sixth each
ROUTER_STD = 0.03


def init_weights(spec: Dict[str, Any], key):
    """The program's parameter tree (its layout is its interface: `layers`
    is a list of segments, each a tuple with one dict per layer of its
    period, stacked over repeats), every leaf bf16, drawn by the benchmark:
    matrices normal(0.02) (output projections 0.02 / sqrt(2 L)), norm
    weights 1 + normal(0.02), the router normal(0.03). Traceable: call
    under jit."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import stack

    cfg = model_config(spec)
    bf16 = jnp.bfloat16
    out_std = STD / (2 * cfg.n_layers) ** 0.5

    def draw(k, name, shape, init):
        n = jax.random.normal(k, shape, jnp.float32)
        if name == "router":
            w = n * ROUTER_STD
        elif init == "one":
            w = 1.0 + n * STD
        else:
            w = n * (out_std if init == "out" else STD)
        return w.astype(bf16)

    def layer(k, kind, half):
        shapes = stack.layer_shapes(cfg, kind, half)
        ks = jax.random.split(k, len(shapes))
        return {name: draw(ks[i], name, *shapes[name])
                for i, name in enumerate(sorted(shapes))}

    k_emb, k_norm, k_layers = jax.random.split(key, 3)
    segments = []
    for first, period, repeats in cfg.segments():
        ks = jax.random.split(jax.random.fold_in(k_layers, first),
                              repeats * len(period))
        ks = ks.reshape(repeats, len(period), *ks.shape[1:])
        # one layer at a time: the f32 draws of a stacked segment of
        # experts would be gigabytes of temporaries
        segments.append(tuple(
            jax.lax.map(lambda k, kind=kind, half=cfg.second_halves[first + i]:
                        layer(k, kind, half), ks[:, i])
            for i, kind in enumerate(period)))
    D = cfg.d_model
    return {"embed": draw(k_emb, "embed", (cfg.vocab_size, D), "w"),
            "layers": segments,
            "final_norm": draw(k_norm, "final_norm", (D,), "one"),
            "lm_head": draw(jax.random.fold_in(k_emb, 1), "lm_head",
                            (D, cfg.vocab_size), "w")}


# -- operations and bytes, from shapes ---------------------------------------


def _attend(spec: Dict[str, Any], key_reads: float,
            query_key_pairs: float) -> Dict[str, float]:
    """Attention of one layer that reads `key_reads` cached tokens (keys
    and values, every KV head's row once) and scores `query_key_pairs`
    (query token, key) pairs in every query head: QK^T and PV."""
    H, KVH, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                  spec["head_dim"])
    return {"flops": 2 * 2 * H * hd * query_key_pairs,
            "bytes": 2 * KVH * hd * BF16 * key_reads}


def paged_decode(spec: Dict[str, Any], context_tokens: float) -> Dict[str, float]:
    """One call of a paged decode-attention kernel (one layer, one step)
    whose sequences read `context_tokens` cached tokens together: one query
    a sequence, so the pairs are the tokens read."""
    return _attend(spec, context_tokens, context_tokens)


def chunk_keys(spec: Dict[str, Any], start: int, tokens: int,
               window: bool) -> Dict[str, float]:
    """What one chunk-attention call (one layer) of `tokens` real tokens
    from position `start` reads and scores: a full layer every key up to
    each row's own, a window layer the last `sliding_window_size` of them.
    -> {"reads": cached tokens read once, "pairs": (query, key) pairs}."""
    W = spec["sliding_window_size"] if window else start + tokens
    first = max(0, start + 1 - W)
    # row c sees keys max(0, start + c + 1 - W) .. start + c
    pairs = sum(min(start + c + 1, W) for c in range(tokens))
    return {"reads": start + tokens - first, "pairs": pairs}


def paged_chunk(spec: Dict[str, Any], reads: float,
                pairs: float = None) -> Dict[str, float]:
    """Chunk-attention calls that read `reads` cached tokens and score
    `pairs` (query, key) pairs together (`chunk_keys` counts both)."""
    return _attend(spec, reads, reads if pairs is None else pairs)


work = {"paged_decode": paged_decode, "paged_decode_window": paged_decode,
        "paged_chunk": paged_chunk, "paged_chunk_window": paged_chunk}


def calls_per_pass(spec: Dict[str, Any], group: str) -> int:
    """Calls of one forward pass (one decode step, one chunk).
    `paged_decode` / `paged_chunk` are every call, windowed or not."""
    k = kinds(spec)
    full, window = k.count("attn"), k.count("swa")
    return {"paged_decode": full + window, "paged_decode_window": window,
            "paged_decode_full": full, "paged_chunk": full + window,
            "paged_chunk_window": window, "paged_chunk_full": full}[group]


def decode_attention_tokens(spec: Dict[str, Any], context: int) -> Dict[str, int]:
    """Cached tokens that one decoded token's attention reads, by kernel
    group: each window layer the last `sliding_window_size` of `context`,
    each full layer all of it."""
    return {"paged_decode_window": calls_per_pass(spec, "paged_decode_window")
            * min(context, spec["sliding_window_size"]),
            "paged_decode": calls_per_pass(spec, "paged_decode_full") * context}


def chunk_attention_work(spec: Dict[str, Any], start: int,
                         tokens: int) -> Dict[str, float]:
    """Operations and bytes of ONE chunk program's attention calls, every
    layer's: `tokens` real tokens from position `start`."""
    out = {"flops": 0.0, "bytes": 0.0}
    for window, group in ((False, "paged_chunk_full"),
                          (True, "paged_chunk_window")):
        seen = chunk_keys(spec, start, tokens, window)
        one = paged_chunk(spec, seen["reads"], seen["pairs"])
        for k in out:
            out[k] += calls_per_pass(spec, group) * one[k]
    return out


# -- the CPU's cut -----------------------------------------------------------

SHRINK = dict(hidden_size=64, moe_ffn_hidden_size=32, num_hidden_layers=8,
              num_attention_heads=8, num_key_value_heads=2, head_dim=8,
              moe_num_primary_experts=8, moe_num_active_primary_experts=3,
              vocab_size=256, max_position_embeddings=512,
              sliding_window_size=16)


def tiny(spec: Dict[str, Any]) -> Dict[str, Any]:
    """Two whole periods of the layer pattern over a window of 16."""
    cut = {**spec, **SHRINK}
    for layout in ("rope_layout", "sliding_window_layout"):
        cut[layout] = spec[layout][:cut["num_hidden_layers"]]
    return cut
