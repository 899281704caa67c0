"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.serve.api``, ``benchmarks/run.py``) call :func:`enable` before
they compile anything, so runs on the same machine reuse each other's
compiled programs.  The directory is part of the cache key, so it is never
built from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (gitignored)
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache is ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
