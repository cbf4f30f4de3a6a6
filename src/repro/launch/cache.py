"""Where JAX keeps its persistent compilation cache.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module
changes nothing. Otherwise the entry points (``chip_smoke.py``, the serve
CLI, the benchmarks) point the cache at ``.jax_cache/`` at the repository
root, so later processes on the same checkout reuse earlier compiles. The
path is fixed on purpose: the cache is found by its directory, and a name
built from a temp dir, a pid or the time would never be found again.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
