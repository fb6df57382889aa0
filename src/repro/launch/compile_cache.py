"""JAX's persistent compilation cache, kept at a fixed place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here changes.  Otherwise the cache goes to ``<repo>/.jax_cache``
(git ignores it).  The path is part of each entry's key, so it never
depends on a temporary name, a process id or the time.  Entry points
call :func:`enable_compile_cache` from ``main()``; importing a module
never does.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
