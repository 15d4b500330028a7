"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples'
``main``) call :func:`enable_compile_cache` once, before their first
compile.  It is deliberately not run at import: a library import must not
change process-wide JAX configuration.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: A fixed directory at the checkout root.  The path is part of a cache
#: entry's key, so a cache that moved between runs would never hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
    it itself; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
