"""The benchmark's plain reference against the test suite's queue BFS."""
import numpy as np
import pytest

import oracles
from bench import reference
from repro.graph import generators as gen

GRAPHS = {
    "rmat_undirected": lambda: gen.rmat(8, 8, directed=False, seed=1),
    "rmat_directed": lambda: gen.rmat(7, 6, directed=True, seed=2),
    "grid": lambda: gen.grid2d(12, 9),
    "disconnected": lambda: gen.disconnected(6, 20, 2.0, seed=3),
}


def _adj(g):
    return reference.adjacency(g.indptr, g.indices, g.n_nodes)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_bfs_rows_equal_queue_bfs(name):
    g = GRAPHS[name]()
    sources = np.random.default_rng(0).choice(g.n_nodes, size=7,
                                              replace=False)
    np.testing.assert_array_equal(reference.bfs_rows(_adj(g), sources),
                                  oracles.bfs_dists(g, sources))


def test_padded_lanes_drop_out():
    g = gen.rmat(6, 4, directed=False, seed=4)
    assert g.m_pad > g.n_edges
    assert _adj(g).nnz == g.n_edges


@pytest.mark.parametrize("name", ["rmat_undirected", "disconnected", "grid"])
def test_component_counts(name):
    g = GRAPHS[name]()
    edges, sizes = reference.components(_adj(g))
    src, dst = g.edge_arrays_np()
    for v in range(g.n_nodes):
        reached = oracles.bfs_dist(g, v) >= 0
        # each undirected edge of the component once: half its lanes
        assert edges[v] == int(reached[src].sum()) // 2
        assert sizes[v] == int(reached.sum())


def test_small_components_count_only_their_own_edges():
    g = gen.disconnected(5, 16, 3.0, seed=9)
    edges, sizes = reference.components(_adj(g))
    deg = np.diff(np.asarray(g.indptr))
    isolated = np.flatnonzero(deg == 0)
    assert len(isolated) >= 8
    assert (edges[isolated] == 0).all() and (sizes[isolated] == 1).all()
    assert edges.max() < g.n_edges // 2       # no component holds all
