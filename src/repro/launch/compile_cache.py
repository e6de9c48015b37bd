"""Where JAX keeps its persistent compilation cache.

A cold full-width train step takes minutes to compile, so every entry
point turns the cache on before its first compile.  The cache is keyed
on its directory, which must therefore never move between runs: a temp
name, a pid or a timestamp would make every run cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache — git-ignored
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set; otherwise the cache goes to :data:`CACHE_DIR`.
    Call it from ``__main__`` code, never at import, so tests keep the
    cache off."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
