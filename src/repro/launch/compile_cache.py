"""JAX's persistent compilation cache for the entry points.

`chip_smoke.py`, `benchmarks/run.py` and `repro.launch.serve` call
`enable_compile_cache()` before their first compile. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this sets
no directory; otherwise the cache goes to `DEFAULT_DIR`, a fixed
directory inside the checkout (listed in `.gitignore`) — the path is part
of the cache key, so it must not move between runs.

The key also covers the programs' metadata. By default JAX leaves it
out, and a program whose only change is a `jax.named_scope` then loads
the executable an earlier build wrote, whose `op_name`s name the earlier
phases or none: a profile would read the wrong names. The metadata's
source paths are made relative to the checkout, so that the same
program in a checkout elsewhere still finds its entry.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(CHECKOUT) + os.sep))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
