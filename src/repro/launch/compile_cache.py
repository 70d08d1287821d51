"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (git-ignored).  The directory is part of what a cache
# entry is found by, so it must not move between runs: never a temp, pid- or
# time-derived path.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache goes to DEFAULT_DIR."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
