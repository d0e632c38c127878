"""JAX's persistent compilation cache, at a place that stays put.

A cold compile of the larger closures takes tens of seconds, so every
entry point (``chip_smoke.py``, the examples, the benchmarks, the
calibration tool) calls :func:`enable_compile_cache` once at start-up —
never at import.  The cache key includes the cache's path, so the path
must not move between runs:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
  sets nothing;
* otherwise the cache lives in :data:`CHECKOUT_CACHE_DIR`, one fixed
  directory inside the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_compile_cache`` (this file is ``src/repro/...``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
