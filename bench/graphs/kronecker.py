"""Graph500 Kronecker generator (graph500.org specification, section
"Graph Generation": the reference ``kronecker_generator``).

``2^scale`` vertices and ``edgefactor * 2^scale`` edge tuples.  Each tuple
picks one quadrant of the adjacency matrix per bit level, with the
initiator probabilities A, B, C and D = 1 - A - B - C; then the vertex
labels and the order of the tuples are permuted at random.  Self-loops and
duplicate tuples are kept, as the specification's generator emits them.
"""

from __future__ import annotations

import numpy as np


def tuples(params: dict, rng: np.random.Generator
           ) -> tuple[np.ndarray, np.ndarray]:
    """The unpermuted edge tuples ``(i, j)``: one quadrant per bit level."""
    scale = int(params["scale"])
    a, b, c, d = (float(x) for x in params["initiator"])
    if abs(a + b + c + d - 1.0) > 1e-12:
        raise ValueError(f"initiator {params['initiator']} does not sum to 1")
    m = int(params["edgefactor"]) << scale
    c_norm = c / (c + d)
    a_norm = a / (a + b)
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > a + b
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        i += ii.astype(np.int64) << bit
        j += jj.astype(np.int64) << bit
    return i, j


def generate(params: dict, seed: int) -> tuple[int, np.ndarray]:
    """``(n, edges)``: the vertex count and an ``(M, 2)`` int64 array of
    edge tuples, drawn from ``seed``, labels and order permuted."""
    rng = np.random.default_rng(seed)
    i, j = tuples(params, rng)
    n = 1 << int(params["scale"])
    labels = rng.permutation(n)
    order = rng.permutation(i.size)
    return n, np.stack([labels[i[order]], labels[j[order]]], axis=1)
