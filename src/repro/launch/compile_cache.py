"""JAX's persistent compilation cache, at a place fixed from outside.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
this module sets nothing. Unset: the cache goes to ``.jax_cache/`` at the
root of the checkout (git-ignored). The path never comes from a temp
name, a pid or the clock, because it is part of the cache key: a
directory that moves never hits.

Entry points call ``enable_compile_cache()`` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
