"""Persistent JAX compilation cache for the entry points.

Called from ``main()`` of the launchers and from ``chip_smoke.py`` —
never at import, so tests and library users keep JAX's own defaults.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set. Otherwise the cache lives at
    ``<checkout>/artifacts/jax_cache``: a fixed path, because the path is
    part of what a later process must find again. Every compile is
    cached, however short: a cold process on the chip pays for all of
    them, and the small kernels are most of the count.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, "artifacts", "jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
