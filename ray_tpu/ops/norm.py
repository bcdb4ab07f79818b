"""Fused RMSNorm / LayerNorm.

RMSNorm gets a Pallas kernel (one VMEM-resident row block per grid step, f32
stats regardless of input dtype); LayerNorm relies on XLA fusion, which is
already optimal for it on TPU. Backward for the Pallas path is the closed
form in XLA — cheap, and it fuses into the surrounding backward graph.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret_mode, platform_dispatch, use_pallas

_DEFAULT_BLOCK_ROWS = 256


def rms_norm_reference(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_pallas(x2d, w, eps, block_rows):
    R, D = x2d.shape
    return pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), x2d.dtype),
        name="rms_norm",
        interpret=interpret_mode(),
    )(x2d, w.reshape(1, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm(x, w, eps):
    return _rms_impl(x, w, eps)


def _rms_impl(x, w, eps):
    D = x.shape[-1]
    rows = x.size // D
    block = min(_DEFAULT_BLOCK_ROWS, rows)
    if not (use_pallas() and rows % block == 0 and D % 128 == 0):
        return rms_norm_reference(x, w, eps)
    return platform_dispatch(
        lambda x, w: _rms_pallas(x.reshape(rows, D), w, eps, block).reshape(x.shape),
        lambda x, w: rms_norm_reference(x, w, eps),
        x,
        w,
    )


def _rms_fwd(x, w, eps):
    return _rms_impl(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xf * inv
    gw = gf * wf
    # d/dx of x * rsqrt(mean(x^2)+eps) * w
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rms_norm.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the last axis. w: [D] scale."""
    return _rms_norm(x, w, eps)


def layer_norm(
    x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, eps: float = 1e-5
) -> jax.Array:
    """LayerNorm over the last axis (XLA — fuses fully on TPU)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(x.dtype)
