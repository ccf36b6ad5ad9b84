"""JAX's persistent compilation cache, placed from outside or in the repo.

The entry points (``python -m repro.launch.serve``, ``python -m
repro.launch.train`` and ``chip_smoke.py``) call
:func:`enable_compile_cache` once, before their first compile.  Library
code and tests never do, so a test run writes no cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

# fixed path at the root of the checkout: the cache directory is part of
# the cache key, so a path built from a temp name, a PID or the time
# would never hit
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as
    ``jax_compilation_cache_dir`` and no other directory is set here.
    Otherwise the cache goes to ``.jax_cache/`` in the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
