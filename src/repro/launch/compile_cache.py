"""JAX's persistent compilation cache for the entry points.

`chip_smoke.py`, `benchmarks/run.py` and `repro.launch.serve` call
`enable_compile_cache()` before their first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this sets
nothing; otherwise the cache goes to `DEFAULT_DIR`, a fixed directory
inside the checkout (listed in `.gitignore`) — the path is part of the
cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
