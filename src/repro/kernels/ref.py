"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def frame_accum_ref(frames: jax.Array) -> jax.Array:
    """(W, n) → (n,)."""
    if jnp.issubdtype(frames.dtype, jnp.floating):
        return jnp.sum(frames.astype(jnp.float32), axis=0).astype(frames.dtype)
    return jnp.sum(frames.astype(jnp.int32), axis=0).astype(frames.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        window: int = 0) -> jax.Array:
    """q: (B,H,S,hd); k,v: (B,KV,S,hd) — causal GQA, materialized softmax."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, S, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bkgqh,bksh->bkgqs", qg, kf) / jnp.sqrt(jnp.float32(hd))
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksh->bkgqh", p, v.astype(jnp.float32))
    return o.reshape(B, H, S, hd).astype(q.dtype)


def ssm_scan_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """(B,S,D,N) linear recurrence via associative scan (matches
    models/ssm.linear_scan)."""
    from repro.models.ssm import linear_scan
    return linear_scan(a.astype(jnp.float32), b.astype(jnp.float32), axis=1)


def rglru_scan_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    from repro.models.ssm import linear_scan
    return linear_scan(a.astype(jnp.float32), b.astype(jnp.float32), axis=1)


def bfs_frontier_ref(src: jax.Array, dst: jax.Array, sigma: jax.Array,
                     dist: jax.Array, level: jax.Array) -> jax.Array:
    """Matches graphs/bfs.py's frontier expansion (segment-sum form)."""
    contrib = jnp.where(dist[src] == level, sigma.astype(jnp.float32)[src],
                        0.0)
    return jax.ops.segment_sum(contrib, dst, num_segments=sigma.shape[0])
