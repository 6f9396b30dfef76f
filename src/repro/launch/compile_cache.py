"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve`` and
``python -m repro.launch.train``) call :func:`enable_compile_cache` before
their first compile; importing a module never does.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX takes the directory from it and
nothing is set here.  Otherwise the cache lives in ``.jax_cache`` at the
root of the checkout: the path is part of what a cache entry is found by,
so it is the same on every call and in every process.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[Path]:
    """Point JAX's persistent cache at :data:`CACHE_DIR` unless
    ``JAX_COMPILATION_CACHE_DIR`` is set; returns the directory set here
    (None when the environment decides)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR
