"""JAX's persistent compilation cache, kept in one fixed place.

Entry points (`chip_smoke.py`, the `main()` of `launch.ingest`,
`launch.workload` and `launch.query`, and `benchmarks/run.py`) call
`enable_compile_cache()` before their first compile.  No library module
calls it, so importing the package never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache is `.jax_cache/` at the
    root of the checkout: a fixed path, because the path is part of
    what a later run must match to hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
