"""Where JAX's persistent compilation cache lives for this repo's entry points.

The cache directory is part of every entry's key, so it must not move
between runs.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads
it itself, so nothing is set here); otherwise the cache is
``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
