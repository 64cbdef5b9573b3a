"""JAX's persistent compilation cache, placed from outside the program.

Entry points that run the model (``chip_smoke.py``, ``repro.launch.serve``,
the examples and ``benchmarks.run``) call :func:`enable_compile_cache`
before their first compile. Tests never call it, so they stay uncached,
and neither does anything on the CPU backend.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here. Otherwise the cache lives at one fixed path
inside the checkout (``.jax_cache``, ignored by git). The directory is part
of what makes a later run find an entry, so it never depends on a temp
name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on for every compile, however short, and
    return the directory it writes to. On the CPU backend it stays off and
    this returns ``None``: CPU runs are rehearsals with short compiles, and
    XLA:CPU reloads cached entries with a host-feature check that warns of
    illegal instructions."""
    if jax.default_backend() == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
