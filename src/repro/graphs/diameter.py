"""Graph-diameter estimation via double-sweep BFS — an ADS workload on the
epoch engine.

One sample picks a vertex v uniformly, runs a BFS sweep to get ecc(v) and
the farthest vertex u = argmax dist(v,·), then a second sweep from u for
ecc(u) (the classic double-sweep lower bound; Magnien–Latapy–Habib).  Both
sweeps reuse the level-synchronous frontier expansion of
:mod:`repro.graphs.bfs` — i.e. the same hot loop the
``kernels/bfs_frontier`` Pallas kernel serves on TPU.  Every sample yields

    lower bound   ecc(u)      ≤ diam
    upper bound   2·ecc(v)    ≥ diam      (triangle inequality)

and a *gap certificate* when 2·ecc(v) − ecc(u) ≤ gap: the best lower bound
seen is then within ``gap`` of the true diameter.  Sampling adapts to the
graph: one sweep from a near-central vertex certifies immediately, while
hard instances keep sampling until the static cap.

Frame layout (all-integer ⇒ exact reductions, INDEXED bit-identity free):

    frame.num  — number of double sweeps
    frame.data — {"cert": int32 scalar — number of gap certificates,
                  "ecc_hist": (L_pad,) int32 — histogram of observed ecc(u)
                  values (L = n+1 bins; a vector leaf so SHARED_FRAME
                  exercises a real reduce-scatter)}

The estimate max{d : ecc_hist[d] > 0} is sum-recoverable — the frame monoid
is elementwise ``+``, so a max-of-samples statistic must be carried as an
occupancy histogram, not a scalar.  Stopping rule:
:class:`~repro.core.stopping.EccentricityGapCondition` (scalar-only verdict
⇒ shard-safe).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.frames import StateFrame
from .bfs import INF, bfs_sssp
from .csr import Graph


def diameter_exact(g: Graph) -> int:
    """Exact diameter by BFS from every vertex (numpy, test oracle).

    Unreachable pairs are ignored (diameter of the largest-distance
    connected pair), matching what double sweeps can observe.
    """
    n = g.n
    indptr = np.asarray(g.indptr)
    # strip the sentinel tail; keep only real neighbor slots
    nbrs = np.asarray(g.indices_padded)[: int(g.m_arcs)]
    best = 0
    for s in range(n):
        dist = np.full(n, -1, np.int64)
        dist[s] = 0
        frontier = [s]
        while frontier:
            nxt = []
            for v in frontier:
                for w in nbrs[indptr[v]:indptr[v + 1]]:
                    if dist[w] < 0:
                        dist[w] = dist[v] + 1
                        nxt.append(int(w))
            frontier = nxt
        best = max(best, int(dist.max()))
    return best


def double_sweep(g: Graph, v: jax.Array, *, max_levels: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """One double sweep from v → (ecc(v), ecc(u)) with u = argmax dist(v,·)."""
    dist_v, _, _ = bfs_sssp(g, v, None, max_levels=max_levels, early_exit=False)
    fin_v = jnp.where(dist_v == INF, -1, dist_v)
    u = jnp.argmax(fin_v).astype(jnp.int32)
    ecc_v = jnp.maximum(jnp.max(fin_v), 0)
    dist_u, _, _ = bfs_sssp(g, u, None, max_levels=max_levels, early_exit=False)
    ecc_u = jnp.max(jnp.where(dist_u == INF, 0, dist_u))
    return ecc_v, ecc_u


def make_sweep_sample_fn(g: Graph, batch: int, *, gap: int = 0,
                         pad_to: Optional[int] = None):
    """Build SAMPLE() — one vectorized round of ``batch`` double sweeps."""
    n = g.n
    bins = n + 1              # ecc ∈ [0, n−1]; bin d counts sweeps with ecc(u)=d
    bins_pad = pad_to or bins
    max_levels = n            # each BFS exits when its frontier empties

    def one(key: jax.Array) -> Tuple[jax.Array, jax.Array]:
        v = jax.random.randint(key, (), 0, n, dtype=jnp.int32)
        ecc_v, ecc_u = double_sweep(g, v, max_levels=max_levels)
        cert = (2 * ecc_v - ecc_u <= gap).astype(jnp.int32)
        return ecc_u.astype(jnp.int32), cert

    def sample_fn(key: jax.Array, carry):
        keys = jax.random.split(key, batch)
        ecc_u, cert = jax.vmap(one)(keys)
        hist = jax.ops.segment_sum(jnp.ones((batch,), jnp.int32), ecc_u,
                                   num_segments=bins_pad)
        data = {"cert": jnp.sum(cert), "ecc_hist": hist}
        return StateFrame(num=jnp.int32(batch), data=data), carry

    return sample_fn


def frame_template(g: Graph, pad_to: Optional[int] = None):
    bins_pad = pad_to or (g.n + 1)
    return {"cert": jnp.zeros((), jnp.int32),
            "ecc_hist": jnp.zeros((bins_pad,), jnp.int32)}


def diameter_estimate(ecc_hist: np.ndarray) -> float:
    """Best lower bound seen: max occupied bin of the ecc(u) histogram."""
    occupied = np.nonzero(np.asarray(ecc_hist) > 0)[0]
    return float(occupied.max()) if occupied.size else 0.0
