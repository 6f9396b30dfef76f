"""Level-synchronous BFS with shortest-path counting + uniform path sampling.

This is SAMPLE() of the paper's Algorithm 1 for KADABRA: pick (s,t) u.a.r.,
run a BFS from s counting shortest paths (σ), then backtrack from t choosing
predecessors with probability σ(u)/Σσ — a uniform random shortest s–t path.

TPU adaptation (DESIGN.md §2/§8): the original uses a sequential
bidirectional BFS per sample; here BFS levels are *edge-parallel*
(segment-sum frontier expansion — dense, MXU/VPU-friendly, vmappable over a
batch of samples) and backtracking gathers ≤ max_degree neighbors per step.
The per-level σ renormalization keeps path counts in float32 range: only
*ratios within one level* matter for sampling, so scaling σ uniformly at a
level is distribution-preserving.

The CSR frontier expansion is the kernel hot spot; ``kernels/bfs_frontier``
is the Pallas TPU version of one level and this file is its oracle.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from .csr import Graph

INF = jnp.int32(0x3FFFFFFF)
_SIGMA_CAP = 1e30


@partial(jax.jit, static_argnames=("max_levels", "early_exit"))
def bfs_sssp(g: Graph, s: jax.Array, t: jax.Array = None, *,
             max_levels: int, early_exit: bool = True
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Distances and (rescaled) shortest-path counts from ``s``.

    Returns ``dist (n,) int32`` (INF if unreachable), ``sigma (n,) f32``
    and ``levels``, the int32 number of levels the loop ran.  If
    ``early_exit`` and ``t`` is given, stops once t's level is complete
    (σ(t) is final at that point — all its predecessors are one level up).
    Under ``vmap`` each lane's ``levels`` is its own; the batched loop runs
    the largest.  The loop's body is the named scope ``bfs_level``.
    """
    n = g.n
    dist = jnp.full((n,), INF, jnp.int32).at[s].set(0)
    sigma = jnp.zeros((n,), jnp.float32).at[s].set(1.0)
    t = jnp.int32(-1) if t is None else t

    def cond(st):
        level, dist, sigma, frontier_size = st
        go = jnp.logical_and(frontier_size > 0, level < max_levels)
        if early_exit:
            go = jnp.logical_and(go, jnp.where(t >= 0, dist[t] == INF, True))
        return go

    @jax.named_scope("bfs_level")
    def body(st):
        level, dist, sigma, _ = st
        # one per-arc gather per level: the (arcs × samples) intermediate is
        # the largest buffer of a batched BFS
        frontier_sigma = jnp.where(dist == level, sigma, 0.0)
        contrib = frontier_sigma[g.src]
        agg = jax.ops.segment_sum(contrib, g.dst, num_segments=n)
        newly = jnp.logical_and(dist == INF, agg > 0.0)
        dist = jnp.where(newly, level + 1, dist)
        # per-level renormalization against float32 overflow: scaling all σ of
        # the new level uniformly preserves the within-level ratios that path
        # sampling uses, so the sampled-path distribution is unchanged.
        mx = jnp.max(jnp.where(newly, agg, 0.0))
        scale = jnp.where(mx > _SIGMA_CAP, _SIGMA_CAP / mx, 1.0)
        sigma = jnp.where(newly, agg * scale, sigma)
        return (level + 1, dist, sigma, jnp.sum(newly.astype(jnp.int32)))

    levels, dist, sigma, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), dist, sigma, jnp.int32(1)))
    return dist, sigma, levels


@partial(jax.jit, static_argnames=("max_levels",))
def eccentricity(g: Graph, s: jax.Array, *, max_levels: int) -> jax.Array:
    dist, _, _ = bfs_sssp(g, s, None, max_levels=max_levels, early_exit=False)
    return jnp.max(jnp.where(dist == INF, 0, dist))


@partial(jax.jit, static_argnames=("max_iters",))
def connected_components(g: Graph, *, max_iters: int = 10_000) -> jax.Array:
    """Component labels via min-label propagation (paper C.1 uses CCs to skip
    disconnected pairs)."""
    n = g.n
    labels = jnp.arange(n, dtype=jnp.int32)

    def cond(st):
        labels, changed, it = st
        return jnp.logical_and(changed, it < max_iters)

    def body(st):
        labels, _, it = st
        neigh_min = jax.ops.segment_min(labels[g.src], g.dst, num_segments=n)
        new = jnp.minimum(labels, neigh_min)
        return new, jnp.any(new != labels), it + 1

    labels, _, _ = jax.lax.while_loop(cond, body, (labels, True, jnp.int32(0)))
    return labels


@partial(jax.jit, static_argnames=("max_len",))
def sample_path(g: Graph, key: jax.Array, s: jax.Array, t: jax.Array,
                dist: jax.Array, sigma: jax.Array, *, max_len: int
                ) -> jax.Array:
    """Uniform random shortest s–t path → bool mask of *internal* vertices.

    Walks backward from t, choosing each predecessor u (a neighbor with
    dist[u] = dist[cur]−1) with probability σ(u)/Σσ via Gumbel-max over the
    ≤ max_degree padded neighbor slots.  If t is unreachable the mask is all
    False (the sample contributes x_i = 0 — the correct estimator term).
    Step i draws from ``split(key, max_len)[i]``, and each step is the named
    scope ``path_step``.  The walk stops once it is at s, after ``dist[t]``
    steps (none if t is unreachable, at most ``max_len``).  Under ``vmap``
    the batched loop runs the deepest lane's steps and the finished lanes
    keep their mask.
    """
    n = g.n
    reachable = dist[t] != INF
    dist_pad = jnp.concatenate([dist, jnp.full((1,), INF, jnp.int32)])
    sigma_pad = jnp.concatenate([sigma, jnp.zeros((1,), jnp.float32)])
    keys = jax.random.split(key, max_len)

    def walking(carry):
        i, cur, _ = carry
        return (i < max_len) & (cur != s) & reachable

    @jax.named_scope("path_step")
    def step(carry):
        i, cur, mask = carry
        nbrs = g.neighbors_padded(cur)                  # (Δ,) with sentinel n
        w = jnp.where(dist_pad[nbrs] == dist[cur] - 1, sigma_pad[nbrs], 0.0)
        gum = -jnp.log(-jnp.log(
            jax.random.uniform(keys[i], w.shape, minval=1e-12, maxval=1.0)))
        scores = jnp.where(w > 0.0, jnp.log(w) + gum, -jnp.inf)
        nxt = nbrs[jnp.argmax(scores)]
        is_internal = jnp.logical_and(nxt != s, nxt != t)
        mask = mask.at[nxt].set(jnp.logical_or(mask[nxt], is_internal))
        return i + 1, nxt, mask

    _, _, mask = jax.lax.while_loop(
        walking, step, (jnp.int32(0), t, jnp.zeros((n,), bool)))
    return mask
