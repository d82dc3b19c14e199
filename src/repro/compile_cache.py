"""JAX's persistent compilation cache for the repo's entry points.

Scripts that drive the engines (``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once before their first compile; the
library itself never does, so importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# Fixed, inside the checkout and listed in .gitignore: the cache key
# includes the path, so a directory that moved between runs never hits.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and no other directory is set; otherwise the cache goes
    to :data:`CACHE_DIR`.  The minimum compile time to cache drops to
    zero: the Pallas kernels compile in well under JAX's default second.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
