"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/``) call
``enable_compile_cache()`` first thing in ``main``; importing a module never
turns the cache on, and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# fixed and inside the checkout (``artifacts/`` is git-ignored): the cache
# directory is part of every entry's key, so it must not move between runs
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / "artifacts" / \
    "jax_cache"


def enable_compile_cache() -> str:
    """Keep every compiled program in the persistent cache; returns its
    directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads the
    variable itself and no other directory is set.  Otherwise the cache goes
    to ``DEFAULT_CACHE_DIR``.  The minimum compile time for caching drops to
    zero so the short kernel compiles are kept too.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
