"""Placement of JAX's persistent compilation cache.

A cold start of the full-width model compiles every program from scratch;
the persistent cache lets a later process of the same code load them
instead.  JAX keys a cache entry by the program, but it only finds an entry
in the directory it was written to, so the directory must be fixed: never a
temporary, pid- or time-based path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the variable
    itself and this sets nothing.  Otherwise the cache goes to
    ``<repo>/.jax_cache``."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
