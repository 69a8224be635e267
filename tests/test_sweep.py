"""The semiring sweep layer: cross-form / cross-semiring equivalence,
parent reconstruction on the batched paths, the weighted engine vs
Dijkstra, and the one-driver structural invariant."""
import re
from pathlib import Path

import numpy as np
import pytest
import jax.numpy as jnp

import repro.core as core
from repro.core import (EngineConfig, UNREACHED, WeightedConfig,
                        apsp_engine, derive_parents, minplus_sssp,
                        multi_source, prepare_graph, prepare_weighted,
                        reconstruct_path, sovm_sssp, sssp, weighted_apsp)
from repro.core import sweep as S
from repro.graph import generators as gen
from repro.graph.csr import CSRGraph

from oracles import bfs_dist, bfs_dists, dijkstra_dists


# -- structural invariant: ONE sweep driver ---------------------------------

def test_exactly_one_while_loop_under_core():
    """The refactor's contract: every core path flows through
    sweep.sweep_loop — no module re-grows its own loop."""
    core_dir = Path(core.__file__).parent
    hits = {}
    for path in sorted(core_dir.glob("*.py")):
        count = len(re.findall(r"lax\.while_loop\(", path.read_text()))
        if count:
            hits[path.name] = count
    assert hits == {"sweep.py": 1}, hits


def test_every_layer_imports_the_sweep_layer():
    core_dir = Path(core.__file__).parent
    for name in ("bovm", "sovm", "bfs", "weighted", "wcc", "distributed",
                 "engine", "centrality"):
        text = (core_dir / f"{name}.py").read_text()
        assert re.search(r"from \. import sweep as S|from \.sweep import",
                         text), name


def test_core_reaches_kernels_only_through_the_registry():
    """The kernel-layer contract: no core module imports a semiring
    kernel package directly — the registry is the single seam, so adding
    a semiring's hardware path never touches core."""
    core_dir = Path(core.__file__).parent
    for path in sorted(core_dir.glob("*.py")):
        for line in path.read_text().splitlines():
            if line.strip().startswith(("import", "from")):
                assert "kernels.bovm" not in line, (path.name, line)
                assert "kernels.tropical" not in line, (path.name, line)
                assert "kernels.counting" not in line, (path.name, line)


def test_weighted_kernel_and_reference_share_the_one_driver(random_weighted):
    """Kernel-backed tropical forms run through the same sweep_loop: the
    sweep counters agree with the reference path on the same graph."""
    g, w = random_weighted(80, 3.0, 37)
    sources = np.arange(8, dtype=np.int32)
    kern = weighted_apsp(g, w, sources,
                         config=WeightedConfig(mode="sparse", source_batch=8,
                                               use_kernel=True))
    ref = weighted_apsp(g, w, sources,
                        config=WeightedConfig(mode="sparse", source_batch=8,
                                              use_kernel=False))
    assert int(kern.sweeps) == int(ref.sweeps)
    np.testing.assert_array_equal(np.asarray(kern.direction_counts),
                                  np.asarray(ref.direction_counts))
    np.testing.assert_array_equal(np.asarray(kern.dist), np.asarray(ref.dist))
    np.testing.assert_allclose(float(kern.edges_touched),
                               float(ref.edges_touched))


# -- cross-form equivalence (boolean semiring) ------------------------------

FAMILIES = {
    "grid": lambda: gen.grid2d(11, 11),
    "rmat": lambda: gen.rmat(8, 4, directed=False, seed=2),
    "er_directed": lambda: gen.erdos_renyi(150, 3.0, seed=9),
    "disconnected": lambda: gen.disconnected(5, 25, 3.0, seed=5),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_push_pull_sparse_agree_with_queue_oracle(family):
    """push ≡ pull ≡ sparse ≡ the queue-BFS oracle on every family."""
    g = FAMILIES[family]()
    sources = np.arange(min(16, g.n_nodes), dtype=np.int32)
    ref = bfs_dists(g, sources)
    for mode in ("push", "pull", "sparse"):
        res = apsp_engine(g, sources,
                          config=EngineConfig(mode=mode, source_batch=16))
        np.testing.assert_array_equal(np.asarray(res.dist), ref,
                                      err_msg=f"{family}/{mode}")


# -- cross-semiring equivalence ---------------------------------------------

@pytest.mark.parametrize("family", ["grid", "rmat", "disconnected"])
def test_minplus_unit_weights_equals_unweighted_sovm(family):
    """Tropical semiring with all-ones weights ≡ boolean SOVM distances."""
    g = FAMILIES[family]()
    w = jnp.ones((g.m_pad,), jnp.float32)
    for src in (0, g.n_nodes // 2):
        sovm_dist = np.asarray(sovm_sssp(g, src).dist).astype(np.float64)
        sovm_dist = np.where(sovm_dist < 0, np.inf, sovm_dist)
        trop = np.asarray(minplus_sssp(g, w, src).dist)
        np.testing.assert_allclose(trop, sovm_dist, err_msg=family)


def test_weighted_apsp_unit_weights_equals_boolean_engine():
    g = gen.watts_strogatz(180, 6, 0.1, seed=7)
    sources = np.arange(16, dtype=np.int32)
    boolean = apsp_engine(g, sources, config=EngineConfig(source_batch=16))
    bdist = np.asarray(boolean.dist).astype(np.float64)
    bdist = np.where(bdist < 0, np.inf, bdist)
    trop = weighted_apsp(g, np.ones(g.m_pad, np.float32), sources,
                         config=WeightedConfig(source_batch=16))
    np.testing.assert_allclose(np.asarray(trop.dist), bdist)


# -- the weighted engine vs Dijkstra ----------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_weighted_apsp_auto_matches_dijkstra(seed, random_weighted):
    """Acceptance: weighted_apsp auto mode == scipy Dijkstra on random
    non-negative graphs."""
    g, w = random_weighted(80 + 30 * seed, 3.0, seed)
    sources = np.arange(min(12, g.n_nodes), dtype=np.int32)
    ref = dijkstra_dists(g, w, sources)
    res = weighted_apsp(g, w, sources,
                        config=WeightedConfig(source_batch=8))
    np.testing.assert_allclose(np.asarray(res.dist), ref, rtol=1e-5)
    assert int(res.direction_counts.sum()) >= int(res.sweeps) > 0


@pytest.mark.parametrize("mode", ["dense", "sparse"])
def test_weighted_fixed_forms_agree(mode, random_weighted):
    g, w = random_weighted(120, 3.0, 11)
    sources = np.arange(10, dtype=np.int32)
    ref = dijkstra_dists(g, w, sources)
    res = weighted_apsp(g, w, sources,
                        config=WeightedConfig(mode=mode, source_batch=8))
    np.testing.assert_allclose(np.asarray(res.dist), ref, rtol=1e-5)
    counts = np.asarray(res.direction_counts)
    idx = ["dense", "sparse"].index(mode)
    assert counts[idx] == counts.sum() > 0


def test_weighted_dynamic_switch_is_exact(random_weighted):
    g, w = random_weighted(100, 4.0, 13)
    sources = np.arange(8, dtype=np.int32)
    ref = dijkstra_dists(g, w, sources)
    res = weighted_apsp(g, w, sources,
                        config=WeightedConfig(source_batch=8))
    np.testing.assert_allclose(np.asarray(res.dist), ref, rtol=1e-5)


def test_weighted_apsp_tiling_and_prepared_reuse(random_weighted):
    g, w = random_weighted(90, 3.0, 17)
    pw = prepare_weighted(g, w)
    sources = np.arange(21, dtype=np.int32)       # 3 tiles of 8
    res = weighted_apsp(pw, sources=sources,
                        config=WeightedConfig(source_batch=8))
    assert res.dist.shape == (21, g.n_nodes)
    ref = dijkstra_dists(g, w, sources)
    np.testing.assert_allclose(np.asarray(res.dist), ref, rtol=1e-5)


# -- parent derivation / path round-trips -----------------------------------

def _check_paths(g, dist_row, parent_row, source):
    adj = g.to_scipy().tocsr()
    dist_row = np.asarray(dist_row)
    reachable = np.flatnonzero(dist_row > 0)
    targets = reachable[:: max(1, len(reachable) // 8)]
    for t in targets:
        path = reconstruct_path(parent_row, source, int(t), g.n_nodes)
        assert path is not None and path[0] == source and path[-1] == t
        assert len(path) - 1 == dist_row[t]
        for a, b in zip(path[:-1], path[1:]):
            assert adj[a, b] != 0


@pytest.mark.parametrize("method", ["auto", "bovm", "sovm"])
def test_sssp_parent_roundtrip_all_methods(method):
    g = gen.watts_strogatz(150, 6, 0.1, seed=21)
    res = sssp(g, 3, method=method)
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  bfs_dist(g, 3))
    _check_paths(g, res.dist, res.parent, 3)


def test_multi_source_auto_parent_roundtrip():
    g = gen.grid2d(9, 9)
    sources = np.arange(6, dtype=np.int32)
    res = multi_source(g, sources, method="auto")
    ref = bfs_dists(g, sources)
    np.testing.assert_array_equal(np.asarray(res.dist), ref)
    parent = np.asarray(res.parent)
    for i, s in enumerate(sources):
        _check_paths(g, res.dist[i], parent[i], int(s))


def test_sovm_on_destination_rows():
    """Single-source SOVM on destination rows: exact distances, valid
    paths, the post-pass's parents, and the same search from a layout
    built once and passed in."""
    g = gen.rmat(10, 16, seed=4, directed=False)
    st = sovm_sssp(g, 5)
    np.testing.assert_array_equal(np.asarray(st.dist), bfs_dist(g, 5))
    _check_paths(g, st.dist, st.parent, 5)
    post = np.asarray(derive_parents(g, st.dist[None, :]))[0]
    np.testing.assert_array_equal(post, np.asarray(st.parent))
    again = sovm_sssp(g, 5, rows=prepare_graph(g).rows)
    for a, b in zip(st, again):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_derive_parents_matches_inloop_sovm():
    """Post-pass parents == in-loop sparse tracking (same tie-break)."""
    g = gen.erdos_renyi(120, 4.0, directed=False, seed=23)
    st = sovm_sssp(g, 0)
    post = np.asarray(derive_parents(g, st.dist[None, :]))[0]
    np.testing.assert_array_equal(post, np.asarray(st.parent))


def _mixed_directed_graph():
    """A directed graph whose CSC differs from its CSR, with a hub of
    in-degree 30 (> 3W for W = 8), vertices of in-degree exactly 4 and
    8, isolated vertices 45..47, and lanes padded well past m."""
    rng = np.random.default_rng(5)
    src = [*range(1, 31), *range(10, 14), *range(20, 28)]
    dst = [0] * 30 + [1] * 4 + [2] * 8
    src += list(rng.integers(3, 45, 90))
    dst += list(rng.integers(3, 45, 90))
    return CSRGraph.from_edges(np.array(src), np.array(dst), 48,
                                    pad_to=384)


ROW_GRAPHS = {
    "directed_mixed": _mixed_directed_graph,
    "kronecker": lambda: gen.rmat(8, 8, seed=3, directed=False),
}


def _lane_form(g):
    """The oracle: the sparse form on CSR lanes, one scatter update per
    lane at the unsorted destinations, parents by max active source."""
    def sparse(f, d, p, step):
        active = f[..., g.src] != 0
        hits = jnp.zeros(d.shape, jnp.bool_).at[..., g.dst].max(active)
        new = hits & (d == UNREACHED)
        pcand = jnp.full(d.shape, -1, jnp.int32).at[..., g.dst].max(
            jnp.where(active, g.src, -1))
        return (new.astype(jnp.int8), jnp.where(new, step, d),
                jnp.where(new, pcand, p))
    return sparse


@pytest.mark.parametrize("width", [4, 8])
@pytest.mark.parametrize("s", [1, 64, 128])
@pytest.mark.parametrize("graph", sorted(ROW_GRAPHS))
def test_row_form_matches_lane_form(graph, s, width):
    """The sparse form on destination rows gives the lane form's new
    frontier, distances and parents (max source id wins), one sweep from
    a random mid-search state and whole searches from sources."""
    g = ROW_GRAPHS[graph]()
    n, n_pad = g.n_nodes, g.n_padded()
    rows = S._dst_rows(g.indptr_t, g.indices_t, n_real=n, width=width)
    forms = {"lanes": _lane_form(g), "rows": S.boolean_forms(
        None, None, rows, n_pad=n_pad, s=s, track_parent=True)[S.SPARSE]}

    rng = np.random.default_rng(s + width)
    f = (rng.random((s, n_pad)) < 0.2).astype(np.int8)
    d = np.where(rng.random((s, n_pad)) < 0.6, -1, 1).astype(np.int32)
    f[:, n:], d[:, n:] = 0, 0
    p = np.full((s, n_pad), -1, np.int32)
    lane_out = forms["lanes"](f, d, p, jnp.int32(2))
    row_out = forms["rows"](f, d, p, jnp.int32(2))
    for a, b in zip(lane_out, row_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    sources = rng.integers(0, n, s)
    f0 = np.zeros((s, n_pad), np.int8)
    f0[np.arange(s), sources] = 1
    d0 = np.where(f0 != 0, 0, -1).astype(np.int32)
    d0[:, n:] = 0
    runs = [S.sweep_loop(
        (forms[layout],), S.make_state(f0, d0, p, n_forms=1),
        max_steps=n) for layout in ("lanes", "rows")]
    np.testing.assert_array_equal(np.asarray(runs[0].dist),
                                  np.asarray(runs[1].dist))
    np.testing.assert_array_equal(np.asarray(runs[0].parent),
                                  np.asarray(runs[1].parent))
    np.testing.assert_array_equal(np.asarray(runs[1].dist)[:, :n],
                                  bfs_dists(g, sources))


@pytest.mark.parametrize("columns", [128, 256])
def test_row_scatter_in_pieces_matches_lane_form(columns, monkeypatch):
    """A vertex axis wider than ``SCATTER_COLUMNS`` is scattered in
    pieces (scale 21 on a v5e); the new frontier and distances stay the
    lane form's, and the pieces alone equal one scatter."""
    monkeypatch.setattr(S, "SCATTER_COLUMNS", columns)
    g = ROW_GRAPHS["kronecker"]()
    n, n_pad = g.n_nodes, g.n_padded()
    assert n_pad > columns
    rows = S.dst_rows(g.indptr_t, g.indices_t, n_real=n)
    sparse = S.boolean_forms(None, None, rows, n_pad=n_pad,
                             s=64)[S.SPARSE]
    rng = np.random.default_rng(columns)
    f = (rng.random((64, n_pad)) < 0.2).astype(np.int8)
    d = np.where(rng.random((64, n_pad)) < 0.6, -1, 1).astype(np.int32)
    f[:, n:], d[:, n:] = 0, 0
    p = np.full((64, n_pad), -1, np.int32)
    for a, b in zip(_lane_form(g)(f, d, p, jnp.int32(2))[:2],
                    sparse(f, d, p, jnp.int32(2))[:2]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    row_dst = rows[1]
    rows_or = rng.random((3, row_dst.shape[0])) < 0.3
    want = np.zeros((3, n_pad), bool)
    np.logical_or.at(want, (slice(None), np.asarray(row_dst)), rows_or)
    np.testing.assert_array_equal(
        np.asarray(S.row_scatter_or((3, n_pad), row_dst, rows_or)), want)


def _mid_search_state(rng, s, n, n_pad):
    f = (rng.random((s, n_pad)) < 0.2).astype(np.int8)
    d = np.where(rng.random((s, n_pad)) < 0.6, -1, 1).astype(np.int32)
    f[:, n:], d[:, n:] = 0, 0
    return f, d, np.full((s, n_pad), -1, np.int32)


@pytest.mark.parametrize("width", [1, 8])
@pytest.mark.parametrize("s", [32, 64, 96, 128])
@pytest.mark.parametrize("graph", sorted(ROW_GRAPHS))
def test_packed_gather_matches_lane_and_row_forms(graph, s, width,
                                                  monkeypatch):
    """Past ``PACK_GATHER_BYTES`` (0 here, so every keyed table packs)
    the sparse form gathers the frontier packed 32 keys a word (96 keys
    pad to 3 words); its new frontier and distances are the lane form's
    and the byte gather's, one sweep from a random mid-search state and
    whole searches from sources."""
    g = ROW_GRAPHS[graph]()
    n, n_pad = g.n_nodes, g.n_padded()
    rows = S._dst_rows(g.indptr_t, g.indices_t, n_real=n, width=width)
    sparse = S.boolean_forms(None, None, rows, n_pad=n_pad,
                             s=s)[S.SPARSE]
    rng = np.random.default_rng(s + width)
    f, d, p = _mid_search_state(rng, s, n, n_pad)
    assert S.gather_words((s, n_pad)) == 0
    bytes_out = sparse(f, d, p, jnp.int32(2))
    monkeypatch.setattr(S, "PACK_GATHER_BYTES", 0)
    assert S.gather_words((s, n_pad)) == -(-s // 32)
    assert S.gather_table_bytes((s, n_pad)) == 4 * -(-s // 32) * n_pad
    packed_out = sparse(f, d, p, jnp.int32(2))
    lane_out = _lane_form(g)(f, d, p, jnp.int32(2))
    for a, b, c in zip(lane_out[:2], bytes_out[:2], packed_out[:2]):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(a))

    sources = rng.integers(0, n, s)
    f0 = np.zeros((s, n_pad), np.int8)
    f0[np.arange(s), sources] = 1
    d0 = np.where(f0 != 0, 0, -1).astype(np.int32)
    d0[:, n:] = 0
    runs = [S.sweep_loop((form,), S.make_state(f0, d0, p, n_forms=1),
                         max_steps=n) for form in (_lane_form(g), sparse)]
    np.testing.assert_array_equal(np.asarray(runs[1].dist),
                                  np.asarray(runs[0].dist))
    assert int(runs[1].step) == int(runs[0].step)
    np.testing.assert_array_equal(np.asarray(runs[1].dist)[:, :n],
                                  bfs_dists(g, sources))


@pytest.mark.parametrize("state", ["single_source", "track_parent"])
def test_packed_gather_leaves_1d_state_and_parents_unpacked(state,
                                                            monkeypatch):
    """1-D single-source state has no key axis, and the in-loop parent
    tree needs each slot's activity: both gather bytes at any table size
    and still give the lane form's frontier, distances and parents."""
    monkeypatch.setattr(S, "PACK_GATHER_BYTES", 0)

    def refuse(*args):
        raise AssertionError("packed gather taken")
    monkeypatch.setattr(S, "packed_row_or", refuse)
    g = ROW_GRAPHS["kronecker"]()
    n, n_pad = g.n_nodes, g.n_padded()
    rows = S.dst_rows(g.indptr_t, g.indices_t, n_real=n)
    s = 64
    f, d, p = _mid_search_state(np.random.default_rng(7), s, n, n_pad)
    if state == "single_source":
        f, d, p, s = f[0], d[0], p[0], 1
        assert S.gather_words(f.shape) == 0
    sparse = S.boolean_forms(None, None, rows, n_pad=n_pad, s=s,
                             track_parent=True)[S.SPARSE]
    for a, b in zip(_lane_form(g)(f, d, p, jnp.int32(2)),
                    sparse(f, d, p, jnp.int32(2))):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
    if state == "single_source":
        st = sovm_sssp(g, 5, rows=rows)
        np.testing.assert_array_equal(np.asarray(st.dist), bfs_dist(g, 5))


def test_engine_batch_on_the_packed_gather(monkeypatch):
    """The batch engine's sparse form past ``PACK_GATHER_BYTES``: 40
    keys on two words, the same distances as the plain BFS."""
    monkeypatch.setattr(S, "PACK_GATHER_BYTES", 0)
    traced = []
    packed = S.packed_row_or
    monkeypatch.setattr(S, "packed_row_or", lambda f, row_src, words: (
        traced.append(words) or packed(f, row_src, words)))
    g = gen.rmat(9, 12, seed=5, directed=False)
    sources = np.arange(0, 400, 10)
    res = apsp_engine(g, sources, config=EngineConfig(mode="sparse",
                                                      source_batch=40))
    assert traced == [2]
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  bfs_dists(g, sources))


def test_derive_parents_weighted(random_weighted):
    g, w = random_weighted(70, 3.0, 29)
    res = weighted_apsp(g, w, np.arange(8),
                        config=WeightedConfig(source_batch=8))
    parent = np.asarray(derive_parents(g, res.dist,
                                       weights=jnp.asarray(
                                           np.where(np.isfinite(w), w,
                                                    np.inf))))
    dist = np.asarray(res.dist)
    src_np, dst_np = g.edge_arrays_np()
    w_np = w[: g.n_edges]
    for i in range(8):
        for v in range(g.n_nodes):
            p = parent[i, v]
            if v == i or not np.isfinite(dist[i, v]):
                continue
            assert p >= 0
            lanes = (src_np == p) & (dst_np == v)
            assert lanes.any()
            assert np.isclose(dist[i, p] + w_np[lanes].min(), dist[i, v],
                              rtol=1e-5)


# -- engine auto == public API auto (satellite: _pick deleted) --------------

def test_public_auto_is_engine_dispatch():
    import repro.core.sssp as sssp_mod
    assert not hasattr(sssp_mod, "_pick")
    g = gen.disconnected(4, 30, 3.0, seed=31)
    res = multi_source(g, np.arange(12), method="auto")
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  bfs_dists(g, np.arange(12)))
    assert np.asarray(res.parent).shape == res.dist.shape
    # eccentricity is the max productive sweep count over sources
    dm = np.asarray(res.dist)
    assert int(res.eccentricity) == int(dm.max())


# -- serving: weighted queries in the batching loop -------------------------

def test_graph_service_weighted_and_unweighted_flush():
    from repro.serve import GraphQuery, GraphService
    g = gen.watts_strogatz(128, 6, 0.1, seed=1)
    rng = np.random.default_rng(0)
    w = rng.uniform(0.5, 3.0, g.m_pad).astype(np.float32)
    svc = GraphService(g, weights=w, max_batch=16)
    for i in range(6):
        svc.submit(GraphQuery(qid=i, source=i,
                              target=None if i % 2 else 100))
    for i in range(6, 12):
        svc.submit(GraphQuery(qid=i, source=i, weighted=True,
                              target=None if i % 2 else 100))
    served = svc.flush()
    assert len(served) == 12 and svc.pending() == 0
    from oracles import dijkstra_dist
    for q in served:
        if q.weighted:
            ref = dijkstra_dist(g, w, q.source)
            if q.target is None:
                np.testing.assert_allclose(q.dist, ref, rtol=1e-5)
            else:
                np.testing.assert_allclose(q.cost, ref[q.target], rtol=1e-5)
        else:
            ref = bfs_dist(g, q.source)
            if q.target is None:
                np.testing.assert_array_equal(q.dist, ref)
            else:
                assert q.hops == int(ref[q.target])


def test_graph_service_rejects_weighted_without_weights():
    from repro.serve import GraphQuery, GraphService
    g = gen.grid2d(8, 8)
    svc = GraphService(g, max_batch=8)
    with pytest.raises(ValueError):
        svc.submit(GraphQuery(qid=0, source=0, weighted=True))
