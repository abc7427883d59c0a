"""JAX's persistent compilation cache, pointed at one fixed directory.

Entry points (``chip_smoke.py``, the scripts under ``benchmarks/`` and
``examples/``) call ``enable_compile_cache()`` before their first compile;
nothing calls it on import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this sets no other directory.  Otherwise the cache
lives at ``<checkout>/.jax_cache`` (listed in ``.gitignore``): the path is
part of what makes a cached entry findable again, so it never depends on a
temporary directory, a process id or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
