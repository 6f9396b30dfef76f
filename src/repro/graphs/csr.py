"""CSR graph container (undirected, unweighted — as in the paper's instances).

Both a CSR view (``indptr``/``indices`` + a max-degree padded variant for
O(Δ) neighbor gathers) and an edge-parallel COO view (``src``/``dst``, each
undirected edge stored as two arcs) are kept: BFS uses the COO view
(segment-sum frontier expansion — the TPU-idiomatic dense form), path
backtracking uses the padded CSR view (O(Δ) per step).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.spans import span


@partial(jax.tree_util.register_dataclass,
         data_fields=("indptr", "indices_padded", "src", "dst"),
         meta_fields=("n", "m_arcs", "max_degree"))
@dataclasses.dataclass(frozen=True)
class Graph:
    n: int                     # static — number of vertices
    m_arcs: int                # static — number of directed arcs (2·|E|)
    max_degree: int            # static
    indptr: jax.Array          # (n+1,) int32
    indices_padded: jax.Array  # (m_arcs + max_degree,) int32, sentinel-padded
    src: jax.Array             # (m_arcs,) int32, sorted by src
    dst: jax.Array             # (m_arcs,) int32

    def degree(self, v: jax.Array) -> jax.Array:
        return self.indptr[v + 1] - self.indptr[v]

    def neighbors_padded(self, v: jax.Array) -> jax.Array:
        """(max_degree,) neighbor ids; slots ≥ degree(v) hold sentinel ``n``."""
        start = self.indptr[v]
        nbrs = jax.lax.dynamic_slice_in_dim(self.indices_padded, start,
                                            self.max_degree)
        slot = jnp.arange(self.max_degree, dtype=jnp.int32)
        return jnp.where(slot < self.degree(v), nbrs, jnp.int32(self.n))


@span("graph.csr")
def from_edges(n: int, edges: np.ndarray) -> Graph:
    """Build an undirected simple Graph from an (E,2) int array of edges.

    Self-loops and duplicate edges are removed; each edge becomes two arcs.
    Returns once the arrays are on the device.
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = edges[edges[:, 0] != edges[:, 1]] if edges.size else edges
    if e.size:
        lo = np.minimum(e[:, 0], e[:, 1])
        hi = np.maximum(e[:, 0], e[:, 1])
        und = np.unique(lo * n + hi)
        lo, hi = und // n, und % n
    else:
        lo = hi = np.zeros(0, dtype=np.int64)
    src = np.concatenate([lo, hi]).astype(np.int32)
    dst = np.concatenate([hi, lo]).astype(np.int32)
    order = np.argsort(src, kind="stable")
    src_s, dst_s = src[order], dst[order]
    indptr = np.zeros(n + 1, dtype=np.int32)
    np.add.at(indptr, src_s + 1, 1)
    indptr = np.cumsum(indptr).astype(np.int32)
    max_degree = max(int((indptr[1:] - indptr[:-1]).max(initial=1)), 1)
    # sentinel-pad the indices tail so dynamic_slice(start, max_degree) is safe
    indices_padded = np.concatenate([dst_s, np.full(max_degree, n, np.int32)])
    return jax.block_until_ready(Graph(
        n=n, m_arcs=int(src_s.size), max_degree=max_degree,
        indptr=jnp.asarray(indptr),
        indices_padded=jnp.asarray(indices_padded),
        src=jnp.asarray(src_s), dst=jnp.asarray(dst_s)))
