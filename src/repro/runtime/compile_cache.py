"""Where JAX keeps its persistent compilation cache.

Entry points (``repro.launch.serve_multi``, ``chip_smoke.py``,
``benchmarks/run.py``) call :func:`use_compile_cache` before their first
compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is set here.  Otherwise the cache goes to ``.jax_cache/`` at
the checkout root: a fixed path, because a cache whose directory moves
between runs never hits.  The test suite does not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
