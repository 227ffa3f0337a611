"""JAX's persistent compilation cache for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it; ``JAX_ENABLE_COMPILATION_CACHE=false`` (the tests set it)
keeps the cache off either way.  Otherwise the cache lives at one fixed path
inside the checkout (``.jax_cache/``, gitignored): the path is part of the
cache key, so a directory that moves never hits.  Entry points
(``chip_smoke.py``, ``launch/train.py``, ``examples/*.py``) call
:func:`enable_compile_cache` once at start-up; library code and tests never
do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (src/repro/launch/ -> repo root)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
