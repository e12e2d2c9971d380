"""Persistent XLA compile cache for the launchers and ``chip_smoke.py``.

A cold run on a chip compiles every kernel and jitted step again; the
persistent cache lets later processes load them instead. Where the
cache lives may be chosen from outside: when ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here. Otherwise the cache
is ``.jax_cache/`` at the checkout root, found from this file's path —
never from the working directory, a temporary name, a pid or the time,
because the directory is part of what makes a later run hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
