"""Persistent compilation cache placement, shared by every entry point.

``launch/train.py``, ``launch/serve.py``, ``launch/tree.py`` and
``chip_smoke.py`` call :func:`init_compile_cache` before anything compiles.
Where the environment sets ``JAX_COMPILATION_CACHE_DIR``, JAX reads it on
its own and nothing is set here.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the path is part of
the cache key, so it is never built from a temporary name, a process id or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "default_cache_dir", "init_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> Path:
    """``<checkout>/.jax_cache`` — this file is ``src/repro/launch/cache.py``."""
    return Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory used."""
    import jax

    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(default_cache_dir())
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
