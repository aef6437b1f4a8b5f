"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a path that moves between runs
never hits. ``JAX_COMPILATION_CACHE_DIR``, when set, is read by jax itself
and wins; otherwise the cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
