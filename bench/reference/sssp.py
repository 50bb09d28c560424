"""Plain reference for the ``sssp`` family: SciPy's Dijkstra on the live
edge set, in float64.  It imports nothing of the program."""

from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra


def reference(num_vertices: int, src: np.ndarray, dst: np.ndarray,
              weight: np.ndarray, sources: np.ndarray) -> np.ndarray:
    """Distances ``[Q, V]`` from each source (inf where unreachable)."""
    src, dst = src.astype(np.int64), dst.astype(np.int64)
    if np.unique(src * num_vertices + dst).size != src.size:
        raise ValueError("parallel edges: csr_matrix would sum their weights")
    adj = csr_matrix((weight.astype(np.float64), (src, dst)),
                     shape=(num_vertices, num_vertices))
    return dijkstra(adj, directed=True, indices=np.asarray(sources))
