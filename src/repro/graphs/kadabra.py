"""KADABRA (Borassi & Natale 2016) on the epoch-based engine — the paper's
case study (§2.3, §4).

Phases (mirroring the original implementation + the paper's C.1 tricks):

1. ``preprocess`` — connected components (skip disconnected pairs cheaply),
   vertex-diameter upper bound via double-sweep BFS, ω from the VC bound.
2. adaptive sampling via :mod:`repro.core.epoch` with any
   :class:`~repro.core.frames.FrameStrategy` — this is where the paper's
   local-/shared-/indexed-frame algorithms run.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.epoch import EpochConfig, EpochState, run_virtual, run_worker
from ..core.frames import FrameStrategy, StateFrame, shard_frame_pad
from ..core.stopping import KadabraCondition, kadabra_omega
from ..runtime.spans import span
from .bfs import INF, bfs_sssp, connected_components, eccentricity, sample_path
from .csr import Graph


@dataclasses.dataclass(frozen=True)
class KadabraParams:
    eps: float = 0.05
    delta: float = 0.1
    batch: int = 16           # samples per sampling round (vectorized SAMPLE)
    rounds_per_epoch: int = 4  # paper's N (App. C.2) in units of rounds
    max_epochs: int = 4096
    xi: float = 0.0            # App. C.3 coordinator-cadence heuristic
    c_omega: float = 0.5


@dataclasses.dataclass(frozen=True)
class Preprocessed:
    omega: float
    vd_upper: int          # vertex-diameter upper bound
    components: jax.Array  # (n,) int32 labels
    diam_levels: int       # BFS level budget


@span("kadabra.preprocess")
def preprocess(g: Graph, eps: float, delta: float, c_omega: float = 0.5,
               seed: int = 0) -> Preprocessed:
    with span("kadabra.components"):
        comps = jax.block_until_ready(connected_components(g))
    # double-sweep: ecc from a random vertex, then from the farthest vertex.
    max_levels = g.n  # worst case; each BFS exits when the frontier empties
    with span("kadabra.sweep"):
        v0 = jnp.int32(seed % g.n)
        dist0, _, _ = bfs_sssp(g, v0, None, max_levels=max_levels,
                               early_exit=False)
        far = jnp.argmax(jnp.where(dist0 == INF, -1, dist0)).astype(jnp.int32)
        ecc = int(eccentricity(g, far, max_levels=max_levels))
    diam_ub = 2 * max(ecc, 1)          # diam ≤ 2·ecc(u) for unweighted graphs
    vd_upper = diam_ub + 1             # vertices on the longest shortest path
    omega = kadabra_omega(eps, delta, vd_upper, c=c_omega)
    return Preprocessed(omega=float(omega), vd_upper=vd_upper,
                        components=comps, diam_levels=diam_ub + 1)


COUNTERS = ("rounds", "bfs_levels", "path_live_steps", "path_steps")


def init_counters() -> dict:
    """The sampler's carry: what its rounds did, counted on the device.

    ``rounds`` sampling rounds; ``bfs_levels`` levels the batched BFS loop
    ran, the deepest lane's in each round; ``path_live_steps`` path-walk
    steps that moved, ``dist(s, t)`` of each lane whose t was reachable;
    ``path_steps`` the path walk's step budget, ``batch × max_len`` a round
    (the walk stops earlier, with the round's deepest lane).
    """
    return {name: jnp.int32(0) for name in COUNTERS}


def make_sample_fn(g: Graph, pre: Preprocessed, batch: int, *,
                   pad_to: Optional[int] = None):
    """Build SAMPLE() — one vectorized round of ``batch`` path samples.

    Frame data: per-vertex counts Σ x_i(v), optionally padded to ``pad_to``
    (for SHARED_FRAME reduce-scatter divisibility).  The carry is
    :func:`init_counters`'s, and comes back with the round counted in it.
    """
    n = g.n
    n_pad = pad_to or n
    max_levels = pre.diam_levels
    max_len = pre.vd_upper

    def one(key: jax.Array):
        ks, kt, kp = jax.random.split(key, 3)
        s = jax.random.randint(ks, (), 0, n, dtype=jnp.int32)
        # t uniform over vertices ≠ s (rejection-free)
        t = (s + 1 + jax.random.randint(kt, (), 0, n - 1, jnp.int32)) % n
        same_cc = pre.components[s] == pre.components[t]
        dist, sigma, levels = bfs_sssp(g, s, t, max_levels=max_levels,
                                       early_exit=True)
        mask = sample_path(g, kp, s, t, dist, sigma, max_len=max_len)
        live = jnp.where(dist[t] != INF, dist[t], 0)
        # disconnected pair ⇒ x_i ≡ 0 (correct estimator term; C.1 trick just
        # skips the BFS work — here the lanes are fixed-shape anyway)
        return jnp.where(same_cc, mask, False), levels, live

    def sample_fn(key: jax.Array, carry):
        keys = jax.random.split(key, batch)
        xs, levels, live = jax.vmap(one)(keys)         # (batch, n) bool
        counts = jnp.sum(xs, axis=0, dtype=jnp.int32)  # Σ x_i(v)
        counts = jnp.pad(counts, (0, n_pad - n))
        carry = {"rounds": carry["rounds"] + 1,
                 "bfs_levels": carry["bfs_levels"] + jnp.max(levels),
                 "path_live_steps": carry["path_live_steps"] + jnp.sum(live),
                 "path_steps": carry["path_steps"] + batch * max_len}
        return StateFrame(num=jnp.int32(batch), data=counts), carry

    return sample_fn


def frame_template(g: Graph, pad_to: Optional[int] = None) -> jax.Array:
    return jnp.zeros((pad_to or g.n,), jnp.int32)


def run_kadabra(g: Graph, params: KadabraParams, *,
                strategy: FrameStrategy = FrameStrategy.LOCAL_FRAME,
                world: int = 1, seed: int = 0,
                pre: Optional[Preprocessed] = None,
                ) -> Tuple[np.ndarray, EpochState, Preprocessed]:
    """End-to-end KADABRA with ``world`` (virtual) parallel workers.

    Returns (btilde estimates (n,), final EpochState, Preprocessed).
    """
    pre = pre or preprocess(g, params.eps, params.delta, params.c_omega, seed)
    pad = shard_frame_pad(g.n, world) if strategy == FrameStrategy.SHARED_FRAME \
        else g.n
    sample_fn = make_sample_fn(g, pre, params.batch, pad_to=pad)
    cond = KadabraCondition(eps=params.eps, delta=params.delta,
                            omega=pre.omega, n_vertices=g.n)

    def check_fn(frame: StateFrame):
        # padded tail (zeros) yields f,g = small values at b̃=0; for the
        # sharded check the per-shard max over real vertices is what matters —
        # padding zeros never *block* stopping because f,g at b̃=0,τ>0 are the
        # minimum of the bound; correctness verified in tests.
        return cond(frame)

    cfg = EpochConfig(strategy=strategy,
                      rounds_per_epoch=params.rounds_per_epoch,
                      max_epochs=params.max_epochs, xi=params.xi)

    if world == 1:
        from ..core.frames import sequential_collectives
        st = run_worker(sample_fn, check_fn, frame_template(g, pad),
                        init_counters(), jax.random.key(seed), cfg,
                        colls=sequential_collectives(),
                        seed_scalar=jnp.asarray(seed, jnp.uint32),
                        worker_id=jnp.int32(0))
        total = st.total
        counts = np.asarray(total.data)[: g.n]
        tau = float(total.num)
    else:
        st = run_virtual(sample_fn, check_fn, frame_template(g, pad),
                         init_counters(), seed, world, cfg)
        # per-worker views of the (replicated or sharded) total
        if strategy == FrameStrategy.SHARED_FRAME:
            counts = np.asarray(st.total.data).reshape(-1)[: g.n]
        else:
            counts = np.asarray(jax.tree.map(lambda x: x[0], st.total.data))[: g.n]
        tau = float(np.asarray(st.total.num)[0] if np.ndim(st.total.num) else st.total.num)

    btilde = counts.astype(np.float64) / max(tau, 1.0)
    return btilde, st, pre
