"""Persistent XLA compilation cache shared by the entry points.

A cold start on the chip compiles every step variant it meets; the cache
lets the next process on the same machine skip those compiles. Only entry
points call ``enable_compile_cache`` (in their ``__main__`` path), so tests
and library users keep JAX's default of no persistent cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout cache directory (listed in .gitignore). The directory
#: is part of each entry's key, so it must not move between runs.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other
    directory is set here; otherwise the cache goes to ``CACHE_DIR``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
