"""JAX's persistent compilation cache, kept where the next run finds it.

A cold start on the chip compiles every serving and training graph; the
persistent cache lets a later process of the same checkout skip that.
The cache directory is part of what makes an entry findable, so it must
not move between runs: never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable itself
    and nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
