"""JAX's persistent compilation cache, kept at one fixed path.

The path is part of what makes a cache entry findable again, so it never
comes from a temporary name, a pid or the clock. Call
:func:`enable_compile_cache` before the first compile of a process.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn the persistent cache on.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    its setting is left alone. Otherwise the cache goes to
    ``<repo root>/.jax_cache``.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
