"""Persistent XLA compilation cache for the entry points.

Every entry point that compiles for a chip (``chip_smoke.py``,
``repro.launch.serve``, ``examples/batch_inference.py``) calls
``enable_compile_cache`` once before it compiles anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory holds the cache and
nothing here names another.  Otherwise the cache lives at one fixed,
git-ignored path inside the checkout, ``<checkout>/.jax_cache``: the
path is part of what makes a later run hit, so it is never derived from
a temp dir, a pid or the time.  Tests do not call ``enable_compile_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else the checkout's."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses.
    Every program is cached, however fast it compiled, so a warm run
    compiles nothing."""
    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
