"""Where JAX keeps its persistent compilation cache.

Entry points that compile for the chip (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable()`` once at start-up; importing the
library sets nothing, so the test suite writes no cache.  The directory is
fixed, because the path is part of the cache's key: a directory named after
a temporary file, a pid or the time would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
