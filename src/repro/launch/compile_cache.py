"""JAX's persistent compilation cache for this repo's entry points.

Entry points (`chip_smoke.py`, `benchmarks/run.py`, the `repro.launch`
drivers) call `enable()` before their first compile.  Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and `enable`
leaves that setting alone.  Otherwise the cache lives at the fixed
`.jax_cache/` in the root of the checkout: the path is part of the cache
key, so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
