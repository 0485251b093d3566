"""Where JAX keeps its persistent compilation cache.

A cache is found again only at the same path, so the path must not move
between runs.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it at
import and ``enable_compile_cache`` leaves that choice alone; otherwise the
cache goes to ``.jax_cache`` at the root of the checkout (git-ignored).
The entry points call this first: ``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve`` and ``benchmarks.run``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory."""
    if os.environ.get(ENV_VAR):
        return jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
