"""Direction-optimizing batched APSP engine: correctness of all sweep
forms, the switch heuristic, graph stats, and the serving integration."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (CentralityConfig, EngineConfig, WeightedConfig,
                        apsp_engine, bfs_queue_numpy, choose_direction,
                        counting_apsp, frontier_stats, prepare_graph,
                        sweep_costs, weighted_apsp, PUSH, PULL, SPARSE,
                        UNREACHED)
from repro.core import sweep as S
from repro.graph import generators as gen
from repro.graph.csr import CSRGraph

from oracles import bfs_dists, bfs_sigmas, dijkstra_dists


def _ref_dists(g, sources):
    return np.stack([bfs_queue_numpy(g, int(s)) for s in sources])


GRAPHS = {
    "er": lambda seed: gen.erdos_renyi(200, 4.0, directed=False, seed=seed),
    "er_directed": lambda seed: gen.erdos_renyi(160, 3.0, seed=seed),
    "ws": lambda seed: gen.watts_strogatz(150, 6, 0.1, seed=seed),
    "grid": lambda seed: gen.grid2d(12, 12),
    "mycielskian": lambda seed: gen.mycielskian(7),
    "disconnected": lambda seed: gen.disconnected(6, 20, 3.0, seed=seed),
}


@pytest.mark.parametrize("family", sorted(GRAPHS))
@pytest.mark.parametrize("seed", [0, 1])
def test_auto_apsp_matches_queue_bfs(family, seed):
    """Property: auto-switch APSP distances equal queue-BFS on random
    graphs across every generator family (the sweep_ref/packed_pull_ref
    oracles are themselves validated against these in test_kernels)."""
    g = GRAPHS[family](seed)
    sources = np.arange(min(24, g.n_nodes), dtype=np.int32)
    res = apsp_engine(g, sources, config=EngineConfig(source_batch=24))
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  _ref_dists(g, sources))
    # counts sum over all source tiles; sweeps is the per-tile max
    assert int(res.direction_counts.sum()) >= int(res.sweeps) > 0


@pytest.mark.parametrize("mode", ["push", "pull", "sparse"])
def test_fixed_modes_agree(mode):
    g = gen.erdos_renyi(180, 5.0, directed=False, seed=7)
    sources = np.arange(16, dtype=np.int32)
    res = apsp_engine(g, sources,
                      config=EngineConfig(mode=mode, source_batch=16))
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  _ref_dists(g, sources))
    # the pinned direction is the only one that ran
    counts = np.asarray(res.direction_counts)
    idx = ["push", "pull", "sparse"].index(mode)
    assert counts[idx] == counts.sum() > 0


def test_dynamic_per_sweep_switching_is_exact():
    """The lax.switch path (the per-sweep heuristic mode="auto" runs)
    must give exact distances."""
    g = gen.watts_strogatz(140, 6, 0.08, seed=5)
    sources = np.arange(16, dtype=np.int32)
    dyn = apsp_engine(g, sources, config=EngineConfig(source_batch=16))
    np.testing.assert_array_equal(np.asarray(dyn.dist),
                                  _ref_dists(g, sources))


def _auto_runs(semiring, g, sources):
    """(run, expected) for one shell under mode="auto" on the reference
    path with no plan: ``run()`` -> (arrays to compare, direction_counts)."""
    if semiring == "boolean":
        cfg = EngineConfig(source_batch=16, use_kernel=False)

        def run():
            r = apsp_engine(g, sources, config=cfg)
            return (r.dist,), r.direction_counts
        return run, (bfs_dists(g, sources),)
    if semiring == "tropical":
        gs, gd = g.edge_arrays_np()
        w = ((gs * 7 + gd * 3) % 9 + 1).astype(np.float32)
        cfg = WeightedConfig(source_batch=16, use_kernel=False)

        def run():
            r = weighted_apsp(g, w, sources, config=cfg)
            return (r.dist,), r.direction_counts
        return run, (dijkstra_dists(g, w, sources),)
    cfg = CentralityConfig(source_batch=16, use_kernel=False)

    def run():
        r = counting_apsp(g, sources, config=cfg)
        return (r.dist, r.sigma), r.direction_counts
    return run, (bfs_dists(g, sources), bfs_sigmas(g, sources))


@pytest.mark.parametrize("semiring", ["boolean", "tropical", "counting"])
def test_auto_reference_path_is_exact_and_reproducible(semiring):
    """mode="auto" with no plan on the reference path: the per-sweep cost
    model chooses every form, the results equal the plain references, and
    two runs report the same direction_counts."""
    g = gen.erdos_renyi(160, 3.0, seed=11)
    sources = np.arange(16, dtype=np.int32)
    run, want = _auto_runs(semiring, g, sources)
    got, counts = run()
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-6)
    _, again = run()
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(again))
    assert int(np.asarray(counts).sum()) > 0


def test_kernel_path_matches_ref():
    """Engine driving the Pallas kernels (interpret=True on CPU)."""
    g = gen.erdos_renyi(100, 4.0, directed=False, seed=3)
    sources = np.arange(8, dtype=np.int32)
    ref = _ref_dists(g, sources)
    for mode in ("push", "pull"):
        res = apsp_engine(g, sources,
                          config=EngineConfig(mode=mode, source_batch=8,
                                              use_kernel=True))
        np.testing.assert_array_equal(np.asarray(res.dist), ref)


def test_source_tiling_and_padding():
    """Sources that don't fill a tile, and more sources than one tile."""
    g = gen.erdos_renyi(150, 4.0, directed=False, seed=11)
    sources = np.arange(37, dtype=np.int32)          # 37 = 2 tiles of 24
    res = apsp_engine(g, sources, config=EngineConfig(source_batch=24))
    assert res.dist.shape == (37, g.n_nodes)
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  _ref_dists(g, sources))


# -- the direction heuristic ------------------------------------------------

def _stats_for(frontier, dist):
    return frontier_stats(jnp.asarray(frontier), jnp.asarray(dist),
                          bs=64, bn=128, bk=128)


def test_heuristic_pull_on_dense_late_frontier():
    """Late-stage dense frontier: every push tile is live, so the packed
    pull sweep (32 nodes/word) is modelled ~4x cheaper."""
    s, n_pad, m_pad = 64, 1024, 65536
    cfg = EngineConfig()
    frontier = np.ones((s, n_pad), np.int8)
    dist = np.full((s, n_pad), int(UNREACHED), np.int32)
    stats = _stats_for(frontier, dist)
    assert float(stats.live_tile_frac) == 1.0
    assert int(choose_direction(stats, n_pad=n_pad, s=s, m_pad=m_pad,
                                cfg=cfg)) == PULL


def test_heuristic_push_on_sparse_early_frontier():
    """Early one-hot frontier: 1/8 of push tiles live -> push is cheapest
    on a dense graph (sparse is priced out by the big edge count)."""
    s, n_pad, m_pad = 64, 1024, 65536
    cfg = EngineConfig()
    frontier = np.zeros((s, n_pad), np.int8)
    frontier[np.arange(s), np.arange(s)] = 1      # all in k-block 0
    dist = np.full((s, n_pad), int(UNREACHED), np.int32)
    stats = _stats_for(frontier, dist)
    assert float(stats.live_tile_frac) == pytest.approx(1 / 8)
    assert int(choose_direction(stats, n_pad=n_pad, s=s, m_pad=m_pad,
                                cfg=cfg)) == PUSH


def test_heuristic_sparse_on_sparse_graph():
    """Few edges: the edge-parallel SOVM sweep undercuts both dense forms
    regardless of occupancy."""
    s, n_pad, m_pad = 64, 1024, 4096
    cfg = EngineConfig()
    frontier = np.ones((s, n_pad), np.int8)
    dist = np.full((s, n_pad), int(UNREACHED), np.int32)
    stats = _stats_for(frontier, dist)
    costs = np.asarray(sweep_costs(stats, n_pad=n_pad, s=s, m_pad=m_pad,
                                   cfg=cfg))
    assert costs.shape == (3,)
    assert int(np.argmin(costs)) == SPARSE


# -- graph stats feeding the engine -----------------------------------------

def test_degree_stats_and_padding():
    g = gen.grid2d(8, 8)                       # n = 64
    st = g.degree_stats()
    assert st.n_nodes == 64
    assert st.max_out_degree == 4
    assert 0 < st.density < 1
    # sentinel must index a dead column: n_padded > n_nodes always
    assert g.n_padded() >= g.n_nodes + 1
    assert g.n_padded() % 128 == 0


# -- the sparse form's destination-row layout -------------------------------

def _kronecker(seed=0, pad_to=None):
    """Undirected Graph500 Kronecker graph, scale 10, edge factor 16."""
    g = gen.rmat(10, 16, seed=seed, directed=False)
    if pad_to is None:
        return g
    src, dst = g.edge_arrays_np()
    return CSRGraph.from_edges(src, dst, g.n_nodes, dedup=False,
                               remove_self_loops=False, pad_to=pad_to)


ROW_LAYOUT_GRAPHS = {
    "kronecker": _kronecker,
    "directed": lambda: gen.erdos_renyi(300, 20.0, seed=3),
    "grid": lambda: gen.grid2d(64, 64),
}


@pytest.mark.parametrize("graph", sorted(ROW_LAYOUT_GRAPHS))
def test_dst_rows_hold_every_lane_once(graph):
    g = ROW_LAYOUT_GRAPHS[graph]()
    n = g.n_nodes
    width = S.row_width(g.m_pad, n)
    assert width == (1 if graph == "grid" else S.ROW_WIDTH)
    slots, row_dst = (np.asarray(a) for a in S.dst_rows(
        g.indptr_t, g.indices_t, n_real=n))
    assert slots.shape == (width, S.row_count(g.m_pad, n))
    row_src = slots.T                                     # (R, W)
    assert row_dst.shape == row_src.shape[:1]
    assert np.all(np.diff(row_dst) >= 0)                  # ascending
    assert np.all(row_src[row_dst == n] == n)             # pad rows
    real = row_src != n
    got = sorted(zip(row_src[real], np.broadcast_to(
        row_dst[:, None], row_src.shape)[real]))
    src, dst = g.edge_arrays_np()
    assert got == sorted(zip(src, dst))                   # each lane once
    # each destination's lanes fill its rows from the front, in CSC order
    for v in np.unique(dst)[:50]:
        mine = row_src[row_dst == v].ravel()
        deg = int(np.sum(dst == v))
        np.testing.assert_array_equal(
            mine[:deg], np.asarray(g.indices_t)[
                int(g.indptr_t[v]):int(g.indptr_t[v + 1])])
        assert np.all(mine[deg:] == n)
        assert len(mine) == -(-deg // width) * width


def test_dst_rows_shape_depends_on_m_pad_and_n_only():
    a, b = _kronecker(0, pad_to=21504), _kronecker(1, pad_to=21504)
    assert a.n_edges != b.n_edges
    la = S.dst_rows(a.indptr_t, a.indices_t, n_real=a.n_nodes)
    lb = S.dst_rows(b.indptr_t, b.indices_t, n_real=b.n_nodes)
    assert [x.shape for x in la] == [x.shape for x in lb]
    assert la[0].shape[1] % 128 == 0


def test_low_degree_grid_runs_on_rows():
    """A 64x64 grid (in-degree 2-4) takes rows of one lane each, in
    destination order, and the engine's sparse sweeps search it
    exactly."""
    grid = gen.grid2d(64, 64)
    row_src, row_dst = prepare_graph(grid).rows
    assert row_src.shape == (1, S.row_count(grid.m_pad, grid.n_nodes))
    assert int(np.sum(np.asarray(row_dst) < grid.n_nodes)) == grid.n_edges
    sources = np.arange(0, 4096, 97, dtype=np.int32)
    res = apsp_engine(grid, sources,
                      config=EngineConfig(mode="sparse", source_batch=16))
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  _ref_dists(grid, sources))
    assert int(res.direction_counts[SPARSE]) == int(
        res.direction_counts.sum()) > 0


def test_auto_on_rows_matches_lanes(monkeypatch):
    """mode=auto's per-sweep choice prices the sparse form by m_pad, so
    the row width changes neither the distances nor the choices: rows of
    8 against rows of one lane each (the lane form in CSC order)."""
    g = _kronecker()
    sources = np.arange(40, dtype=np.int32)
    cfg = EngineConfig(source_batch=16)
    rows = apsp_engine(g, sources, config=cfg)
    monkeypatch.setattr(S, "dst_rows", lambda *a, n_real: S._dst_rows(
        *a, n_real=n_real, width=1))
    assert prepare_graph(g).rows[0].shape[0] == 1
    lanes = apsp_engine(g, sources, config=cfg)
    np.testing.assert_array_equal(np.asarray(rows.dist),
                                  np.asarray(lanes.dist))
    np.testing.assert_array_equal(np.asarray(rows.direction_counts),
                                  np.asarray(lanes.direction_counts))
    assert int(rows.direction_counts[SPARSE]) > 0
    np.testing.assert_array_equal(np.asarray(rows.dist),
                                  _ref_dists(g, sources))


def test_to_pull_packed_roundtrip():
    from repro.core import unpack_bits
    g = gen.erdos_renyi(100, 3.0, seed=4)
    n_pad = g.n_padded()
    packed = g.to_pull_packed(n_pad)
    assert packed.shape == (n_pad, n_pad // 32)
    dense = np.asarray(g.to_dense_padded(n_pad))
    got = np.asarray(unpack_bits(packed, n_pad))
    np.testing.assert_array_equal(got, dense.T != 0)


# -- serving integration ----------------------------------------------------

def test_graph_queries_served_alongside_decode():
    import jax
    from repro._attic.models import transformer as T
    from repro._attic.lm_serving import Request, ServingEngine
    from repro.serve import GraphQuery, GraphService
    cfg = T.LMConfig(name="t", n_layers=1, d_model=32, n_heads=2, n_kv=1,
                     d_head=16, d_ff=64, vocab=64)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    g = gen.watts_strogatz(128, 6, 0.1, seed=1)
    eng = ServingEngine(params, cfg, slots=1, max_len=32,
                        graph_service=GraphService(g, max_batch=8))
    eng.submit(Request(rid=0, prompt=np.array([1, 2], np.int32), max_new=2))
    for i in range(11):   # 11 queries > one 8-wide micro-batch
        eng.submit_graph(GraphQuery(qid=i, source=i,
                                    target=None if i % 2 else 100))
    eng.run_to_completion()
    done = eng.graph_service.completed
    assert len(done) == 11 and len(eng.completed) == 1
    for q in done:
        ref = bfs_queue_numpy(g, q.source)
        if q.target is None:
            np.testing.assert_array_equal(q.dist, ref)
        else:
            assert q.hops == int(ref[q.target])
        assert q.t_done >= q.t_submit


def test_graph_service_standalone_flush():
    from repro.serve import GraphQuery, GraphService
    g = gen.grid2d(10, 10)
    svc = GraphService(g, max_batch=8)
    for i in range(5):
        svc.submit(GraphQuery(qid=i, source=i * 3, target=99))
    served = svc.flush()
    assert len(served) == 5 and svc.pending() == 0
    for q in served:
        assert q.hops == int(bfs_queue_numpy(g, q.source)[99])
