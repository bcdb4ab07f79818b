"""Where JAX's persistent compilation cache lives for this checkout.

The path is part of the cache key's environment: a directory that moves
(a temporary name, a pid, a time) never hits. So it is either what the
operator set in JAX_COMPILATION_CACHE_DIR, or one fixed place.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first jit; -> its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and this
    sets no other directory. Otherwise the cache goes to
    `<checkout>/.jax_cache`. Either way the compile listeners are on from
    here (`tracing.watch_compiles`): every program after is timed."""
    from . import tracing

    tracing.watch_compiles()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
