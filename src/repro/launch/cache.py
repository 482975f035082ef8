"""Persistent XLA compile cache for the command-line entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.sweep``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; nothing calls it at
library import, so tests and library users keep JAX's own defaults.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this sets nothing.  Otherwise the cache lives at a fixed
``<repo root>/.jax_cache`` (listed in ``.gitignore``): the directory is
part of the cache key, so a path made from a temporary name, a pid or a
time would never hit.  ``LIBTPU_INIT_ARGS`` is left as the environment
set it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: <repo root>/.jax_cache — this file is <repo>/src/repro/launch/cache.py
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
