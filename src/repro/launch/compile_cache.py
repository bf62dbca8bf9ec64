"""Persistent JAX compilation cache for the entry points.

`enable_compile_cache()` is called by entry points (`chip_smoke.py`,
`benchmarks/run.py`), never on import and never by tests. Where
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and nothing here
overrides it; otherwise the cache lives at `.jax_cache/` in the checkout
(listed in .gitignore). That directory is fixed so that the next run
finds what this one compiled: never a temporary directory, a pid or a
timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; return its directory."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        DEFAULT_DIR.mkdir(exist_ok=True)
        cache_dir = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every executable: a search plan's many small compiles add up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
