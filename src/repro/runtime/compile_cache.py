"""JAX's persistent compilation cache at a path that never moves.

The path is part of the cache's key, so a directory named after a temp
dir, a pid or the time would never be hit again. Entry points that pay
for a large compile (``chip_smoke.py``) call :func:`enable` once, before
their first jit; tests do not.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this leaves the cache there. Otherwise the cache goes to
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
