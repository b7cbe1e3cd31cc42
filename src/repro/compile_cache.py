"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile; nothing
calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, that
directory is the cache and no other is ever set.  Otherwise the cache
sits at the fixed ``<checkout>/.jax_cache``: the directory is part of
what a later process must find again, so it is never built from a
temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
