"""JAX's persistent compilation cache, pointed at a fixed directory.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` before their first
compile; library modules never do, so importing the package changes no
global JAX state.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and this sets no
  directory of its own.
* Otherwise the cache lives in ``<checkout>/.jax_cache`` (ignored by git).
  The path is fixed — never derived from a temp name, a pid or the time —
  because it is part of what makes a later run find the entries.

Either way every program is cached, however fast it compiled: JAX skips
programs that compile in under a second by default, and on a TPU v5e the
k-bucket serving programs at ldoor size compile in 0.4-0.6 s.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_ENV", "default_cache_dir", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """``<checkout>/.jax_cache``: this file is ``<checkout>/src/repro/core``."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
