"""Kernel dispatch policy: Pallas on TPU, XLA everywhere else.

Dispatch is per *lowering platform* (`lax.platform_dependent`), not per
process: one process can trace computations for both a real TPU and a
virtual CPU mesh (the fake-cluster test pattern), so a process-wide
`jax.default_backend()` check misclassifies one of them. The TPU branch
only ever lowers on TPU, so Pallas kernels there never need interpret
mode; the default branch is the XLA reference implementation.
"""

from __future__ import annotations

import os
import threading

import jax
import jax.numpy as jnp

_interp_override = threading.local()


def _forced() -> "bool | None":
    """RAY_TPU_FORCE_PALLAS=1 forces Pallas (interpret mode off-TPU — used
    by kernel correctness tests), =0 forces the XLA fallback everywhere."""
    forced = os.environ.get("RAY_TPU_FORCE_PALLAS")
    if forced is None:
        return None
    return forced not in ("0", "false", "")


def use_pallas() -> bool:
    """True when the Pallas TPU path may be taken this process (gates only
    the cheap shape checks; real selection is platform_dispatch)."""
    forced = _forced()
    if forced is not None:
        return forced
    return True


def interpret_mode() -> bool:
    """Pallas interpret mode for the branch currently being traced.

    platform_dispatch sets a per-branch override (TPU branch: compiled;
    any other platform: interpret) — the decision must follow the LOWERING
    platform, not the process default backend, because one process can
    trace for both a real TPU and a virtual CPU mesh."""
    override = getattr(_interp_override, "value", None)
    if override is not None:
        return override
    return jax.default_backend() != "tpu"


def _with_interp(fn, interpret: bool):
    def run(*args):
        prev = getattr(_interp_override, "value", None)
        _interp_override.value = interpret
        try:
            return fn(*args)
        finally:
            _interp_override.value = prev

    return run


def platform_dispatch(pallas_fn, xla_fn, *args):
    """Run `pallas_fn(*args)` when lowering for TPU, `xla_fn(*args)` on any
    other platform. Both must return identical shapes/dtypes/pytrees.
    RAY_TPU_FORCE_PALLAS overrides (1 = pallas everywhere, interpret mode
    on non-TPU lowerings; 0 = XLA everywhere)."""
    forced = _forced()
    if forced is False:
        return xla_fn(*args)
    tpu_branch = _with_interp(pallas_fn, False)
    if forced is True:
        return jax.lax.platform_dependent(
            *args, tpu=tpu_branch, default=_with_interp(pallas_fn, True)
        )
    return jax.lax.platform_dependent(*args, tpu=tpu_branch, default=xla_fn)


def slot_order(live):
    """live bool [B] -> int32 [B]: the slot whose blocks grid program b
    holds where a program of a slot that is not live does nothing: its own
    if live, else the last live slot before it, else the first live one
    (else 0). Consecutive programs then name the same blocks, which the
    pipeline neither fetches nor writes back again; the programs have to
    run in order (`"arbitrary"`)."""
    B = live.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.min(jnp.where(live, idx, B))
    return jnp.where(last >= 0, last, jnp.where(first < B, first, 0))
