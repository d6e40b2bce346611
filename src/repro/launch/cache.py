"""Where JAX keeps its persistent compilation cache.

Entry points (the serve CLI, the benchmark CLI, ``chip_smoke.py``) call
:func:`enable_compile_cache` once at start-up; importing this module changes
nothing. The cache directory is part of every entry's key, so it must not
move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself and nothing here overrides it), else the
fixed ``.jax_cache/`` at the root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """``<checkout>/.jax_cache``, resolved from this package's location."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Every compile is cached, however short: the query kernels compile in
    about a second each, under JAX's default one-second floor.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(default_cache_dir())
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
