"""Persistent XLA compile cache: placed from outside, or at one fixed path.

The cache directory is part of what a run can find again, so it must not
move: a directory made fresh per run never hits. Entry scripts
(chip_smoke.py, bench.py, the serving replica main) call :func:`enable`
once before their first compile; nothing calls it at import.
"""
from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    this sets nothing in code — whoever placed the cache owns it,
    thresholds included. Otherwise the cache lives in the checkout's
    git-ignored ``.jax_cache`` and keeps every program (both thresholds
    at 0), since the programs worth caching here include sub-second
    serving buckets."""
    outer = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outer:
        return outer
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return DEFAULT_DIR
