"""JAX's persistent compilation cache, in one place.

``chip_smoke.py``, ``examples/serve_retrieval.py`` and the benchmarks call
:func:`enable_compile_cache` before they compile anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
no other path.  Otherwise the cache goes to ``<repo>/.jax_cache`` — a
fixed path, because the directory is part of what a cached entry is
found by (``.gitignore`` lists it).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
