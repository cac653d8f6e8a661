"""JAX's persistent compilation cache, at a place that can be set from outside.

A cold process recompiles every kernel and step it runs; with the cache on,
the next process that compiles the same program on the same backend reads
it back.  The cache's path is part of what makes an entry findable, so it
never moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
otherwise ``.jax_cache/`` at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Caches every compile, the one-second kernel compiles included (JAX
    skips compiles under a second by default)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
