"""JAX's persistent compilation cache for the program's entry points.

``chip_smoke.py``, the examples and the benchmark runner call
:func:`enable_compile_cache` once, before their first compile; library code
and tests never do. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
and this module sets no directory. Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): a directory that moved
between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# the Pallas kernels compile in one to two seconds; JAX's default threshold
# (one second) would skip some of them
_MIN_COMPILE_SECS = 0.25


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_SECS)
    return jax.config.jax_compilation_cache_dir
