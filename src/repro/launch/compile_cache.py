"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` before their first compile.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here.  Otherwise the cache lives at ``<checkout>/.jax_cache`` -- a fixed
path, because the path is part of the cache's key: a directory that moves
between runs never hits.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
