"""The plain reference: breadth-first search and Graph500 edge counts on
the host, in numpy and scipy, sharing no code with the program.

Both read the graph as the benchmark made it: the sorted CSR lanes
(``indptr``, ``indices``) with the sentinel ``n`` in the padded lanes.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph


def adjacency(indptr, indices, n: int) -> sp.csr_matrix:
    """The (n, n) 0/1 adjacency of the CSR lanes; padded lanes drop out."""
    indptr = np.asarray(indptr, np.int64)
    m = int(indptr[n])
    cols = np.asarray(indices[:m], np.int32)
    return sp.csr_matrix((np.ones(m, np.int8), cols, indptr), shape=(n, n))


def bfs_rows(adj: sp.csr_matrix, sources) -> np.ndarray:
    """Hop distances from each source -> (len(sources), n) int32, -1 where
    unreachable.  Level-synchronous: each level's frontier is the set of
    unvisited vertices with an in-edge from the last level."""
    sources = np.asarray(sources, np.int64)
    n = adj.shape[0]
    pull = adj.T.tocsr().astype(np.float32)
    dist = np.full((n, len(sources)), -1, np.int32)
    frontier = np.zeros((n, len(sources)), np.float32)
    frontier[sources, np.arange(len(sources))] = 1.0
    dist[sources, np.arange(len(sources))] = 0
    level = 0
    while frontier.any():
        level += 1
        found = (pull @ frontier > 0) & (dist < 0)
        dist[found] = level
        frontier = found.astype(np.float32)
    return np.ascontiguousarray(dist.T)


def components(adj: sp.csr_matrix):
    """Per vertex, (the undirected edges of its connected component, each
    counted once; the vertices of that component).  The first is the
    Graph500 TEPS numerator of a search from the vertex, the second the
    count of vertices such a search reaches."""
    n_comp, label = csgraph.connected_components(adj, directed=False)
    lanes = np.bincount(label, weights=np.diff(adj.indptr),
                        minlength=n_comp).astype(np.int64)
    sizes = np.bincount(label, minlength=n_comp)
    return lanes[label] // 2, sizes[label]
