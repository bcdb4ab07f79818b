"""Seeded weights as the benchmark hands them to the program: the family's
`init_weights` (benchmark/families/), made on the device in bf16 inside one
jit (an f32 tree of the whole model does not fit beside anything else on a
16 GiB chip: PR 23, finding 3).

The weights are the benchmark's, not the program's `init_params`: the plain
reference takes the same tree, and nothing the program has made."""

from __future__ import annotations

from typing import Any, Dict

from . import common


def seed_key(seed: int):
    """--seed may pass 2**31: fold it in two halves that int32 holds."""
    import jax

    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def make_weights(spec: Dict[str, Any], seed: int):
    import jax

    init_weights = common.family(spec).init_weights
    return jax.jit(lambda key: init_weights(spec, key))(seed_key(seed))
