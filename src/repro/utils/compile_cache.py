"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Compiling the city-scale scans takes seconds to minutes on a TPU, so the
scripts that drive them (``chip_smoke.py``, ``examples/cityscale_cache_sim.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile.  The library never calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (gitignored).  The path is fixed, never built from a
# temp name, a pid or the time, so a later run in the same checkout hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    it is left alone; otherwise the cache goes to ``DEFAULT_DIR``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
