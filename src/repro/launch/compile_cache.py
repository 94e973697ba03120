"""JAX persistent compilation cache location for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
alone.  Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
path, because the path is part of the cache key, and a git-ignored one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
