"""JAX's persistent compilation cache for the repository's entry points.

A cold process on the chip recompiles the index build, the wave ladder and
the batch walker; the persistent cache lets a later process reuse them. The
directory is part of what a cache entry is found by, so it must not move
between runs: it is either what ``JAX_COMPILATION_CACHE_DIR`` says (JAX
reads that variable itself) or the fixed ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache(repo_root: Optional[str] = None) -> str:
    """Turns the persistent compile cache on and returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here. Otherwise the cache goes to
    ``<repo_root>/.jax_cache`` (default: this checkout's root).
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = os.path.join(os.path.abspath(repo_root or REPO_ROOT), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
