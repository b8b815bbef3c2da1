"""Where the command-line entry points keep JAX's persistent compile cache.

Only entry points call :func:`enable_compile_cache`, first thing in their
``main``; importing the library never turns the cache on.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and the cache
is left alone.  Otherwise the cache goes to ``<repo root>/.jax_cache``: a
fixed path, because the path is part of every cache key, so a directory
that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compile cache; returns the directory in use."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
