"""JAX's persistent compilation cache for the repository's entry points.

A jitted program that a later process compiles again at the same shapes
is read back from this cache instead.  Within one process
``run_ensemble`` keeps the compiled scan of each static signature itself
and compiles only a new one; this cache spares a later process that
compile.
"""
from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, names the directory and no
    other is set.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (the path is part of the cache key, so a
    directory that moved would never hit).  Call it at the start of an
    entry point, never on import."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
