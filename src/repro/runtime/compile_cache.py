"""JAX's persistent compilation cache, for the program's entry points.

A cold process compiles every plan again, which on a TPU takes seconds per
plan.  The entry points (``chip_smoke.py``, ``repro.launch.serve``)
call :func:`enable_compile_cache` once at start-up;
library code never does, so importing the package changes no JAX setting.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and nothing else
    is configured (JAX reads the variable itself).  Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, because a cache whose
    directory moves between runs never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
