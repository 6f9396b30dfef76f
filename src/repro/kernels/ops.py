"""jit'd public wrappers around the Pallas kernels.

On a TPU backend every wrapper runs its kernel compiled; on any other
backend it runs the pure-jnp oracle from ``ref.py``.  Interpret mode is for
tests only, which call the kernel modules directly.
"""

from __future__ import annotations

import jax

from . import ref as _ref
from .bfs_frontier import bfs_frontier as _bfs_kernel
from .flash_attention import flash_attention as _fa_kernel
from .frame_accum import frame_accum as _fa_accum_kernel
from .rglru_scan import rglru_scan as _rg_kernel
from .ssm_scan import ssm_scan as _ssm_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def frame_accum(frames):
    if not _on_tpu():
        return _ref.frame_accum_ref(frames)
    return _fa_accum_kernel(frames)


def flash_attention(q, k, v, *, window: int = 0):
    if not _on_tpu():
        return _ref.flash_attention_ref(q, k, v, window=window)
    return _fa_kernel(q, k, v, window=window)


def ssm_scan(a, b):
    if not _on_tpu():
        return _ref.ssm_scan_ref(a, b)
    return _ssm_kernel(a, b)


def rglru_scan(a, b):
    if not _on_tpu():
        return _ref.rglru_scan_ref(a, b)
    return _rg_kernel(a, b)


def bfs_frontier(src, dst, sigma, dist, level):
    if not _on_tpu():
        return _ref.bfs_frontier_ref(src, dst, sigma, dist, level)
    return _bfs_kernel(src, dst, sigma, dist, level)
