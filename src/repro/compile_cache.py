"""Where JAX keeps its persistent compilation cache.

A cold process compiles the engine's executables from scratch; the
persistent cache lets the next process on the same machine find them
again.  The cache key includes the directory, so it must not move
between runs: it is either the directory the environment names in
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself — nothing else is
set here) or the fixed ``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["use_compile_cache"]

#: the checkout root: this file is ``<root>/src/repro/compile_cache.py``
_ROOT = Path(__file__).resolve().parents[2]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and return
    that path.  Call before anything compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = str(_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
