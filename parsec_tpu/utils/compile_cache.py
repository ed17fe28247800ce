"""Where JAX keeps its persistent compilation cache.

One place decides it, before the first compile: chip_smoke.py, bench.py
and every TpuDevice call place_compile_cache().  The directory is part of
the cache's key (a cache that moves never hits), so it is fixed:
JAX_COMPILATION_CACHE_DIR when the environment sets it (JAX reads that
itself; nothing else is set), else `<checkout>/.jax_cache` (git-ignored).
"""
from __future__ import annotations

import os

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Idempotent; takes effect only before the
    process's first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    if jax.config.jax_compilation_cache_dir != CHECKOUT_CACHE:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
