"""Random geometric graph of the DIMACS10 ``rgg_n_2_<k>_s0`` family
(10th DIMACS Implementation Challenge, Graph Partitioning and Graph
Clustering: "rgg" instances).

``2^scale`` points uniform in the unit square, and an edge between every
two points closer than ``radius_factor * sqrt(ln n / n)``; the family uses
the factor 0.55.
"""

from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree


def radius(n: int, factor: float) -> float:
    return factor * float(np.sqrt(np.log(n) / n))


def generate(params: dict, seed: int) -> tuple[int, np.ndarray]:
    """``(n, edges)``: the vertex count and an ``(E, 2)`` int64 array of the
    undirected edges, from points drawn from ``seed``."""
    n = 1 << int(params["scale"])
    points = np.random.default_rng(seed).random((n, 2))
    pairs = cKDTree(points).query_pairs(radius(n, float(params["radius_factor"])),
                                        output_type="ndarray")
    return n, pairs.astype(np.int64)
