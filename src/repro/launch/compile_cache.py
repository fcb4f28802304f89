"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once at start-up. A cache
directory given from outside, in ``JAX_COMPILATION_CACHE_DIR``, is used as
it is (JAX reads the variable itself) and nothing is set in code.
Otherwise the cache lives at ``<repo root>/.jax_cache``: a fixed path,
because the path is part of what a later process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
