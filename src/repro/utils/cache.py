"""Where JAX keeps its persistent compilation cache.

The path is part of the cache's key, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX itself (it reads
the variable); otherwise the cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

#: the fixed cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def init_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
