"""JAX's persistent compilation cache, at a place the next run finds.

Call ``enable_compile_cache()`` from an entry point's ``main()`` — never at
import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here sets a directory.  Otherwise the cache lives in
``.jax_cache/`` at the root of the checkout: a fixed path, because the
path is part of what the cache is keyed on, and a directory that moves
(a temp name, a PID, a time) never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
