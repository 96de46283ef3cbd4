"""JAX's persistent compilation cache for the command-line entry points.

Entry points call :func:`enable_compile_cache` first thing, so a second run
of the same program loads its executables instead of compiling them again.
Library code and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path, because the cache directory is part of
# what a cached entry is found by — a path built from a temporary name, a
# pid or the time would never hit.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable
    itself and no path is set here; otherwise the cache goes to
    :data:`CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
