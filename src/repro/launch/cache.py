"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so it must be a fixed path to ever
hit: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself
and nothing else is set here), otherwise ``<checkout>/.jax_cache``.  Call
:func:`init_compile_cache` before the first compilation.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def init_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
