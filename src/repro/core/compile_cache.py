"""JAX's persistent compilation cache, kept at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile, so a second run in
the same checkout reads back what the first compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/core/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``,
    a fixed path (never one built from a temp name, pid or time), so that
    every run in the checkout finds the same entries.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
