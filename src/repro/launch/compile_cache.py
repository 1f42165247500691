"""JAX's persistent compilation cache for the entry points.

A full-width decode step or train step takes tens of seconds to compile;
the cache lets the next process on the same machine load it instead. The
cache key includes the directory, so the directory must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself,
and nothing is set here), otherwise ``.jax_cache/`` at the root of the
checkout (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses. Call it
    before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
