"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py`` and the examples) calls :func:`use_compile_cache`
before its first compile, so a second process on the same host finds the
programs the first one compiled.

The rule:

  * when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    module sets no other directory;
  * otherwise the cache lives at ``<repo>/.jax_cache``.  The path is part
    of the cache's key, so it is fixed: no tempdir, pid or timestamp.

The ETL's per-bucket programs each compile in well under JAX's default
one-second threshold for persisting an entry, so the threshold is lowered
to zero: they are the programs a cold start pays for.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
