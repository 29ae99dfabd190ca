"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points that compile many programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before any other
JAX work, so a second run of the same code reuses the compiled programs of
the first. The cache path is part of the cache's key, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads it
itself and nothing is set in code), otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` — this file lives at ``<checkout>/src/repro/``
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
