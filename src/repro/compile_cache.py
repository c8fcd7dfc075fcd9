"""Placement of JAX's persistent compilation cache, for entry points only.

Scripts that run the system (``chip_smoke.py``, the benchmark collectors)
call :func:`enable_compile_cache` first thing in ``main``; the library
never calls it, so importing ``repro`` leaves JAX's cache settings alone.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no other path.
- Otherwise the cache lives at ``<checkout>/.jax_compile_cache`` (listed in
  ``.gitignore``).  The path is fixed — never temporary, per-process or
  per-run — because a cache directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    from_env = os.environ.get(CACHE_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
