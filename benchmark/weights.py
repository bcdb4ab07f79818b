"""The model as the benchmark hands it to the program: a `ModelConfig` built
from the configuration's own file, and seeded weights made on the device in
bf16 inside one jit (an f32 tree of the whole model does not fit beside
anything else on a 16 GiB chip: PR 23, finding 3).

The weights are the benchmark's, not the program's `init_params`: the plain
reference takes the same tree, and nothing the program has made."""

from __future__ import annotations

from typing import Any, Dict

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


def seed_key(seed: int):
    """--seed may pass 2**31: fold it in two halves that int32 holds."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


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


def make_weights(spec: Dict[str, Any], seed: int):
    import jax

    return jax.jit(lambda key: init_weights(spec, key))(seed_key(seed))
