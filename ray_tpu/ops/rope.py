"""Rotary position embeddings (RoPE).

Pure XLA: elementwise, so the compiler fuses it into the surrounding
projections; a Pallas kernel would add nothing. Implements the
half-rotation (Llama/NeoX) convention with optional NTK/linear scaling.
`yarn_inv_freq` / `yarn_mscale`: the inverse frequencies and the score
factor of yarn (arXiv:2309.00071, as the DeepSeek-V3 family applies it),
for whoever builds a table or turns by position (models/stack.py `_turn`).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


def rope_frequencies(
    head_dim: int,
    max_len: int,
    theta: float = 10000.0,
    scaling: Optional[float] = None,
    dtype=jnp.float32,
):
    """Precompute (cos, sin) tables: each [max_len, head_dim // 2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(max_len, dtype=jnp.float32)
    if scaling is not None:
        pos = pos / scaling
    ang = jnp.outer(pos, inv_freq)
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """Inverse frequencies [dim // 2] of `dim` rotary lanes under yarn: lane
    pair i keeps theta^(-2i/dim) where it turns more than `beta_fast` times
    over the `original_max` positions it was trained on, takes it divided
    by `factor` (interpolated) where it turns less than `beta_slow` times,
    and a linear blend between the two pairs where those counts fall."""
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def pair_of(turns: float) -> float:  # the pair that turns so often
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = (jnp.arange(dim // 2, dtype=jnp.float32) - lo) / max(hi - lo, 1e-3)
    keep = 1.0 - jnp.clip(ramp, 0.0, 1.0)
    return extra / factor * (1.0 - keep) + extra * keep


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """yarn's attention factor: 0.1 mscale ln(factor) + 1 (1 where nothing
    is stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Rotate x [B, T, H, D] by the tables; positions [B, T] selects rows
    (defaults to arange(T) — pass real positions for decode/packed batches)."""
    B, T, H, D = x.shape
    if positions is None:
        c = jax.lax.dynamic_slice_in_dim(cos, 0, T)[None, :, None, :]
        s = jax.lax.dynamic_slice_in_dim(sin, 0, T)[None, :, None, :]
    else:
        c = cos[positions][:, :, None, :]
        s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1)
    return out.astype(x.dtype)
