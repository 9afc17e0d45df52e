"""JAX's persistent compilation cache, kept at one fixed place.

A run that finds its programs in the cache skips their compilation. The
cache directory is part of what makes an entry findable again, so it
never moves: `JAX_COMPILATION_CACHE_DIR` when the environment sets it
(JAX reads that variable itself, and nothing here overrides it), else
`.jax_cache/` at the root of the checkout.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program this process
    compiles, however quickly, and return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env_dir or CHECKOUT_CACHE_DIR
