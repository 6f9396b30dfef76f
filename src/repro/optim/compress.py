"""Gradient compression for slow (cross-pod / DCN) links.

int8 block-quantization with stochastic rounding + **error feedback**:
the residual of each quantization step is carried and added to the next
step's gradient, making the compression unbiased-in-the-limit (standard
EF-SGD construction).  Applied only to the ``pod`` axis reduction — ICI
all-reduces stay bf16/f32.

``compressed_psum`` accumulates int8 payloads in int32 (512 devices × 127
< 2³¹, no overflow), so hardware reduction still applies.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


def quantize_int8(x: jax.Array, key: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Per-tensor scale, stochastic rounding. Returns (q int8, scale f32)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-12) / 127.0
    y = xf / scale
    noise = jax.random.uniform(key, x.shape, minval=-0.5, maxval=0.5)
    q = jnp.clip(jnp.round(y + noise), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(grads: PyTree, ef: PyTree, key: jax.Array,
                    axis_name: str) -> Tuple[PyTree, PyTree]:
    """psum(grads) over ``axis_name`` with int8 payload + error feedback.

    Returns (reduced f32 grads ≈ mean over axis, new error-feedback state).
    Scales are max-combined across the axis so the int8 grids agree.
    """
    world = jax.lax.axis_size(axis_name)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    ef_leaves = jax.tree_util.tree_leaves(ef)
    keys = jax.random.split(key, len(leaves))
    out, new_ef = [], []
    for g, e, k in zip(leaves, ef_leaves, keys):
        gc = g.astype(jnp.float32) + e
        # agree on a shared scale (1 scalar all-reduce per tensor)
        local_max = jnp.maximum(jnp.max(jnp.abs(gc)), 1e-12)
        scale = jax.lax.pmax(local_max, axis_name) / 127.0
        noise = jax.random.uniform(k, g.shape, minval=-0.5, maxval=0.5)
        q = jnp.clip(jnp.round(gc / scale + noise), -127, 127)
        new_ef.append(gc - q * scale)                  # residual feedback
        summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
        out.append(summed.astype(jnp.float32) * scale / world)
    return (jax.tree_util.tree_unflatten(treedef, out),
            jax.tree_util.tree_unflatten(treedef, new_ef))
