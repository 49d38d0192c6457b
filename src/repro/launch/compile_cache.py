"""The persistent XLA compile cache every entry point shares.

A cold full-width run compiles every serving step, calibration pass and
kernel; the cache lets the next process on the same machine skip them. The
cache key includes the directory, so it must not move between runs: never
a temporary, pid- or time-derived path.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
    else is set; otherwise the cache lives at ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
