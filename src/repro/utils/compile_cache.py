"""JAX's persistent compilation cache, placed from outside the program.

The entry points (`launch.train`, `launch.serve`, `chip_smoke.py`) call
:func:`enable_compile_cache` once, before their first compile; importing
``repro`` never turns the cache on. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and no directory is set here. Otherwise the cache
lives at a fixed ``<checkout>/.jax_cache`` (git-ignored), so a second run
from the same checkout finds the programs the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
