"""JAX's persistent compilation cache for the entry points.

A run finds only what earlier runs wrote to the same directory, so the
cache lives at one fixed place:
``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself), else ``<repo>/.jax_cache`` (git-ignored).  Entry points call
``enable_compile_cache()`` from ``main()``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
