"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` first, before they compile
anything; no library module calls it at import, and the tests leave the
cache off.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it by itself, and nothing
  here overrides it.
* otherwise: ``<repo>/.jax_cache``. The path is fixed (no temp name, pid
  or time) because it is part of the cache key: a directory that moves
  never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
