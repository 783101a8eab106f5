"""Persistent XLA compilation cache for the entry points that drive a device.

A cold device run compiles every program it touches, and every capacity
doubling of the SPMD executor is a new static shape.  The persistent cache
keeps those executables across processes.  Its directory is part of the
cache key, so it must never move between runs: either the one the
environment names (``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself) or
a fixed ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile and
    return its directory.  With ``JAX_COMPILATION_CACHE_DIR`` set, JAX uses
    that directory and no other is configured here."""
    import jax

    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable, not only the ones that took over a second:
    # a retry's recompile of a small operator is what a warm run must skip
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
