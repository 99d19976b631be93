"""JAX's persistent compilation cache, configured in one place.

Every launch entry point (and ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile, so a second run
of the same program on the same machine loads its executables instead
of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed and inside the checkout (listed in .gitignore): the directory
# is part of the cache key, so a path that moved would never hit
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    wins: nothing else is configured. Otherwise the cache goes to
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
