"""Plain reference of a KADABRA query, independent of the program.

It imports nothing of ``repro`` and takes nothing the program made: it
builds its own graph from the configuration's edge tuples, runs its own
preprocessing, draws the program's sample stream from the seed with
``jax.random`` alone, and redoes every sample with a NumPy BFS in float64.

What it has to agree on with the program is the definition of a run:

* The graph: undirected, simple, the arcs of a vertex in the order
  "higher neighbours ascending, then lower neighbours ascending", and a
  row of ``max_degree`` slots per vertex, the slots past its degree unused.
* Preprocessing: a double sweep from vertex 0 to the vertex farthest from
  it (the first such vertex), ecc = that vertex's eccentricity, a diameter
  bound 2 * ecc, a vertex-diameter bound and a BFS level budget 2 * ecc + 1,
  and omega = (c / eps^2) * (floor(log2(VD - 2)) + 1 + ln(1 / delta)).
* The stream: worker w of W starts from ``split(key(seed), W)[w]``; epoch e
  samples with the first half of the e-th split of that chain; an epoch is
  ``split(k_e, rounds)``, a round ``split(k_r, batch)``, a sample
  ``ks, kt, kp = split(k, 3)``, ``s = randint(ks, 0, n)``,
  ``t = (s + 1 + randint(kt, 0, n - 1)) % n``, and step i of the walk back
  from t draws ``uniform(split(kp, VD)[i], (max_degree,))`` in (1e-12, 1).
* A sample is the set of inner vertices of the shortest s-t path chosen by
  Gumbel-max over the predecessors u of the current vertex, with the score
  log(sigma_s(u)) - log(-log(U_slot)): a uniform shortest path.
* The stopping check of KADABRA (Borassi and Natale, appendix B) with
  delta_L = delta_U = delta / (2 n) for every vertex.

``dtype`` is float64 for the reference and bfloat16 for the control: the
same computation, with sigma, the Gumbel scores and the stopping bounds
rounded to bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

BF16 = np.dtype(ml_dtypes.bfloat16)


# --------------------------------------------------------------- the graph
def build_graph(n: int, edges: np.ndarray) -> dict:
    """CSR arrays of the simple undirected graph on ``edges``."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    pairs = np.unique(lo * n + hi)
    lo, hi = pairs // n, pairs % n
    src = np.concatenate([lo, hi])
    dst = np.concatenate([hi, lo])
    # per vertex: higher neighbours ascending, then lower ones ascending
    order = np.lexsort((dst, dst < src, src))
    src, dst = src[order], dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    max_degree = max(int(np.diff(indptr).max(initial=1)), 1)
    return {"n": n, "m_arcs": int(src.size), "max_degree": max_degree,
            "indptr": indptr, "src": src, "dst": dst}



# ----------------------------------------------------------------- the BFS
def bfs(g: dict, s: int, t: int = -1, dtype=np.float64
        ) -> tuple[np.ndarray, np.ndarray]:
    """Distances (-1 where unreached) and shortest-path counts from ``s``,
    level by level.  With a target ``t`` it stops at t's level, as soon as
    a neighbour of t is reached: the walk back from t reads the distance of
    t and the counts of the levels before it, and nothing else."""
    n, indptr, dst = g["n"], g["indptr"], g["dst"]
    dist = np.full(n, -1, np.int64)
    sigma = np.zeros(n, dtype)
    dist[s] = 0
    sigma[s] = 1
    t_nbrs = dst[indptr[t]:indptr[t + 1]] if t >= 0 else None
    frontier = np.array([s], np.int64)
    level = 0
    while frontier.size and (t < 0 or dist[t] < 0):
        if t >= 0 and (dist[t_nbrs] == level).any():
            dist[t] = level + 1
            break
        starts = indptr[frontier]
        degs = indptr[frontier + 1] - starts
        offsets = np.repeat(starts - (np.cumsum(degs) - degs), degs)
        nbr = dst[offsets + np.arange(offsets.size)]
        weight = np.repeat(sigma[frontier].astype(np.float64), degs)
        fresh = dist[nbr] < 0
        paths = np.bincount(nbr[fresh], weights=weight[fresh], minlength=n)
        frontier = np.flatnonzero(paths)
        level += 1
        dist[frontier] = level
        sigma[frontier] = paths[frontier].astype(dtype)
    return dist, sigma


def preprocess(g: dict, eps: float, delta: float, c: float = 0.5) -> dict:
    dist0, _ = bfs(g, 0)
    far = int(np.argmax(np.where(dist0 < 0, -1, dist0)))
    dist1, _ = bfs(g, far)
    ecc = int(dist1.max())
    diam_ub = 2 * max(ecc, 1)
    vd = diam_ub + 1
    omega = (c / eps ** 2) * (math.floor(math.log2(max(vd, 4) - 2)) + 1
                              + math.log(1.0 / delta))
    return {"connected": bool((dist0 >= 0).all()), "vd_upper": vd,
            "diam_levels": diam_ub + 1, "omega": omega}


# -------------------------------------------------------------- the stream
def worker_key(seed: int, world: int, worker: int) -> jax.Array:
    return jax.random.split(jax.random.key(seed), world)[worker]


def epoch_key(key: jax.Array, epoch: int) -> jax.Array:
    """The sampling key of ``epoch`` on the chain that starts at ``key``."""
    for _ in range(epoch):
        key = jax.random.split(key)[1]
    return jax.random.split(key)[0]


@partial(jax.jit, static_argnames=("rounds", "batch", "n", "steps", "slots"))
def _stream(key, *, rounds, batch, n, steps, slots):
    def sample(k):
        ks, kt, kp = jax.random.split(k, 3)
        s = jax.random.randint(ks, (), 0, n, jnp.int32)
        t = (s + 1 + jax.random.randint(kt, (), 0, n - 1, jnp.int32)) % n
        bits = jax.vmap(lambda kk: jax.random.bits(kk, (slots,), jnp.uint32))(
            jax.random.split(kp, steps))
        return s, t, bits

    def round_(kr):
        return jax.vmap(sample)(jax.random.split(kr, batch))

    return jax.vmap(round_)(jax.random.split(key, rounds))


def uniform(bits: np.ndarray) -> np.ndarray:
    """``jax.random.uniform(minval=1e-12, maxval=1.0)`` of the given bits."""
    f = ((bits >> np.uint32(9)) | np.uint32(0x3F800000)).view(np.float32)
    lo = np.float32(1e-12)
    u = (f - np.float32(1.0)) * (np.float32(1.0) - lo) + lo
    return np.maximum(lo, u)


# ------------------------------------------------------------- the samples
def walk(g: dict, s: int, t: int, dist: np.ndarray, sigma: np.ndarray,
         bits: np.ndarray, dtype=np.float64) -> list:
    """Inner vertices of the shortest path chosen from t back to s."""
    if dist[t] < 0:
        return []
    indptr, dst = g["indptr"], g["dst"]
    inner, cur = [], t
    for step in range(bits.shape[0]):
        if cur == s:
            break
        nb = dst[indptr[cur]:indptr[cur + 1]]
        cand = (dist[nb] == dist[cur] - 1) & (sigma[nb] > 0)
        u = uniform(bits[step, :nb.size])
        with np.errstate(divide="ignore", invalid="ignore"):
            if dtype == BF16:
                u = u.astype(BF16).astype(np.float32)
                gumbel = (-np.log(-np.log(u))).astype(BF16)
                logs = np.log(sigma[nb].astype(np.float32)).astype(BF16)
                score = (logs + gumbel).astype(BF16).astype(np.float64)
            else:
                score = (np.log(sigma[nb].astype(np.float64))
                         - np.log(-np.log(u.astype(np.float64))))
        nxt = int(nb[np.argmax(np.where(cand, score, -np.inf))])
        if nxt != s and nxt != t:
            inner.append(nxt)
        cur = nxt
    return inner


def frame(g: dict, pre: dict, seed: int, world: int, worker: int, epoch: int,
          *, rounds: int, batch: int, dtype=np.float64) -> np.ndarray:
    """Per-vertex counts of one worker's epoch: the program's delta frame."""
    key = epoch_key(worker_key(seed, world, worker), epoch)
    s, t, bits = jax.device_get(_stream(
        key, rounds=rounds, batch=batch, n=g["n"], steps=pre["vd_upper"],
        slots=g["max_degree"]))
    counts = np.zeros(g["n"], np.int64)
    for r in range(rounds):
        for i in range(batch):
            si, ti = int(s[r, i]), int(t[r, i])
            dist, sigma = bfs(g, si, ti, dtype)
            counts[walk(g, si, ti, dist, sigma, bits[r, i], dtype)] += 1
    return counts


# ---------------------------------------------------------- the stop check
def kadabra_bounds(counts: np.ndarray, tau: int, eps: float, delta: float,
                   omega: float, dtype=np.float64) -> dict:
    """max f, max g over the vertices, and the verdict, for the state with
    per-vertex ``counts`` after ``tau`` samples."""
    def cast(x):
        return np.asarray(x, np.float64).astype(dtype).astype(np.float64)

    n = counts.size
    big_l = cast(-math.log(delta / (2.0 * n)))
    t = cast(max(tau, 1))
    r = cast(omega / t)
    b = cast(counts / t)
    f = cast((big_l / t) * cast(cast(1 / 3 - r) + cast(np.sqrt(
        cast((1 / 3 - r) ** 2 + cast(2 * b * omega / big_l))))))
    g = cast((big_l / t) * cast(cast(1 / 3 + r) + cast(np.sqrt(
        cast((1 / 3 + r) ** 2 + cast(2 * b * omega / big_l))))))
    max_f, max_g = float(f.max()), float(g.max())
    stop = tau > 0 and ((max_f <= eps and max_g <= eps) or tau >= omega)
    return {"max_f": max_f, "max_g": max_g, "stop": bool(stop)}
