"""Where JAX keeps its persistent compilation cache.

A later run finds the cache only at the same path, so the path is either
the one the environment names or a fixed directory inside the checkout,
never one built from a temp name, a pid or a time.
"""
from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.
    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no
    other directory is set here."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
