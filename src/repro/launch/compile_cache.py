"""Where JAX keeps its persistent compilation cache.

Called by the entry points only (``chip_smoke.py``, ``launch.serve``,
``launch.train``, ``benchmarks/run.py``) — never when a library module is
imported.  The cache key includes the directory, so the default is a fixed
path inside the checkout: a directory that moved between runs would never
hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on at :func:`cache_dir`.

    JAX reads ``$JAX_COMPILATION_CACHE_DIR`` itself, so when it is set no
    other directory is configured here."""
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
