"""The program's trace names (repro.obs): device scopes in the compiled
batch program, and host spans in a CPU profile of the facade and the
serving loop."""
import glob
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro as dawn
from repro import obs
from repro.core import engine as E
from repro.core import sweep as S
from repro.graph import generators as gen
from repro.serve.engine import GraphQuery

SRC = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
SCOPE_NAMES = re.compile(r"dawn\.[a-z_]+(?:\.[a-z_]+)*")


@pytest.fixture(scope="module")
def graph():
    return gen.watts_strogatz(256, 6, 0.1, seed=0)


def batch_op_names(g, forced_dir):
    """``dawn.*`` components of the compiled ``_run_batch``'s op names."""
    pg = E.prepare_graph(g)
    cfg = E.EngineConfig(source_batch=8, use_kernel=False)
    dense = forced_dir is None
    adj = pg.adj if dense else jnp.zeros((1, 1), jnp.int8)
    adj_pull = pg.adj_pull if dense else jnp.zeros((1, 1), jnp.uint32)
    text = E._run_batch.lower(
        adj, adj_pull, *pg.rows, pg.deg,
        jnp.arange(8, dtype=jnp.int32), jnp.int32(8), cfg=cfg,
        n_real=g.n_nodes, n_pad=pg.n_pad, m_pad=g.m_pad,
        max_steps=g.n_nodes,
        use_kernel=False, interpret=True,
        forced_dir=forced_dir).compile().as_text()
    return set(SCOPE_NAMES.findall(text))


@pytest.mark.parametrize("forced_dir,want", [
    (S.SPARSE, {"sweep.sparse", "sweep.test", "batch.init"}),
    (None, {"sweep.push", "sweep.pull", "sweep.sparse", "sweep.choose",
            "sweep.test", "batch.init"}),
], ids=["pinned_sparse", "dynamic_switch"])
def test_scopes_reach_compiled_op_names(graph, forced_dir, want):
    names = batch_op_names(graph, forced_dir)
    assert names == {obs.PREFIX + n for n in want}
    assert names <= set(obs.NAMES)


def test_fused_scope_wraps_the_block():
    f0 = jnp.zeros((2, 8), jnp.int8).at[:, 0].set(1)
    d0 = jnp.where(f0 != 0, 0, -1)

    def fused(f, d, step, n_run):
        return jnp.zeros_like(f), d + 1, jnp.int32(1), jnp.bool_(True)

    def run(f, d):
        return S.sweep_loop((), S.make_state(f, d), max_steps=4,
                            fused=fused, fused_steps=2).dist

    text = jax.jit(run).lower(f0, d0).compile().as_text()
    assert "dawn.sweep.fused" in set(SCOPE_NAMES.findall(text))


@pytest.mark.parametrize("name,want", [
    ("push", "push"), ("pull", "pull"), ("sparse", "sparse"),
    ("sparse_ref", "sparse"), ("sparse_form", "sparse"),
    ("dense_form", "dense"), ("min_label", "min_label"),
    ("sweep", "form"), ("pushy", "form")])
def test_form_names(name, want):
    def form():
        pass
    form.__name__ = name
    assert obs.form_name(form) == want
    assert obs.PREFIX + "sweep." + want in obs.NAMES


def test_every_builder_form_has_a_known_name(graph):
    pg = E.prepare_graph(graph)
    w = jnp.ones(graph.m_pad, jnp.float32)
    forms = (*S.boolean_forms(pg.adj, pg.adj_pull, pg.rows,
                              n_pad=pg.n_pad, s=8),
             *S.tropical_forms(None, graph.src, graph.dst, w)[1:],
             S.minlabel_form(graph.src, graph.dst),
             *S.counting_forms(pg.adj, graph.src, graph.dst,
                               n_pad=pg.n_pad, s=8))
    assert "form" not in {obs.form_name(f) for f in forms}


def test_every_name_used_in_src_is_listed():
    used = set()
    call = re.compile(r"obs\.(?:span|spanned|scope)\(\s*\"([^\"]+)\"")
    for path in SRC.rglob("*.py"):
        for name in call.findall(path.read_text()):
            used.add(obs.PREFIX + name)
    assert len(used) >= 12
    assert used <= set(obs.NAMES)
    assert len(set(obs.NAMES)) == len(obs.NAMES)


def profile(tmp_path, fn):
    """Run ``fn`` under the profiler -> the ``dawn.*`` host events as
    (name, start_ns, end_ns, stats)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(obs.PREFIX)]


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_apsp_spans_nest(graph, tmp_path):
    h = dawn.prepare(graph, source_batch=8, use_kernel=False)
    jax.block_until_ready(h.apsp(np.arange(20)))       # compile outside
    events = profile(tmp_path, lambda: jax.block_until_ready(
        h.apsp(np.arange(20))))
    top, = [e for e in events if e[0] == "dawn.apsp"]
    assert top[3] == {"semiring": "boolean", "n_sources": 20}
    names = [e[0] for e in events if e is not top and inside(e, top)]
    assert names.count("dawn.engine.plan") == 1
    assert names.count("dawn.engine.collect") == 1
    tiles = [e for e in events if e[0] == "dawn.engine.tile"]
    assert [t[3] for t in tiles] == [{"valid": 8, "tile": 8},
                                     {"valid": 8, "tile": 8},
                                     {"valid": 4, "tile": 8}]
    assert all(inside(t, top) for t in tiles)


def test_serving_spans_nest(graph, tmp_path):
    now = [0.0]
    svc = dawn.prepare(graph, use_kernel=False).serve(
        max_batch=8, row_cache_size=0, max_wait=0.0, clock=lambda: now[0])
    svc.submit(GraphQuery(qid=0, source=1, target=2))
    svc.tick()                                         # compile outside

    def serve():
        for i in range(3):
            svc.submit(GraphQuery(qid=i + 1, source=3 + i, target=9))
        now[0] = 0.25
        assert len(svc.tick()) == 3

    events = profile(tmp_path, serve)
    assert [e[0] for e in events].count("dawn.serve.submit") == 3
    tick, = [e for e in events if e[0] == "dawn.serve.tick"]
    flush, = [e for e in events if e[0] == "dawn.serve.flush"]
    assert inside(flush, tick)
    assert flush[3]["rows"] == 3
    assert flush[3]["tile"] == svc.config.source_batch >= 3
    assert flush[3]["wait_ms"] == pytest.approx(250.0)
    parts = [e for e in events if e[0].startswith("dawn.serve.flush.")]
    assert [e[0] for e in parts] == ["dawn.serve.flush.wait",
                                     "dawn.serve.flush.copy",
                                     "dawn.serve.flush.fill"]
    assert all(inside(e, flush) for e in parts)


def plan_stats(g, mode, tmp_path):
    """The ``dawn.engine.plan`` span's args of an 8-source ``apsp``."""
    h = dawn.prepare(g, source_batch=8, use_kernel=False, mode=mode)
    jax.block_until_ready(h.apsp(np.arange(8)))        # compile outside
    events = profile(tmp_path, lambda: jax.block_until_ready(
        h.apsp(np.arange(8))))
    plan, = [e for e in events if e[0] == "dawn.engine.plan"]
    return plan[3]


@pytest.mark.parametrize("kind", ["rows", "none"])
def test_plan_span_carries_the_sparse_layout(kind, tmp_path):
    g = gen.rmat(10, 16, seed=0, directed=False)
    stats = plan_stats(g, "sparse" if kind == "rows" else "push", tmp_path)
    assert stats["sparse_layout"] == kind
    if kind == "rows":
        rows = S.row_count(g.m_pad, g.n_nodes)
        assert stats["rows"] == rows
        assert stats["lane_fill"] == pytest.approx(
            g.n_edges / (rows * S.ROW_WIDTH))
        assert 0.5 < stats["lane_fill"] < 1
    else:
        assert "rows" not in stats and "lane_fill" not in stats


@pytest.mark.parametrize("mode", ["sparse", "push"])
def test_plan_span_carries_table_and_operand_bytes(mode, tmp_path):
    g = gen.rmat(10, 16, seed=0, directed=False)
    stats = plan_stats(g, mode, tmp_path)
    n_pad = E.prepare_graph(g).n_pad
    assert stats["table_bytes"] == n_pad * 8
    if mode == "sparse":       # (1, 1) int8 and uint32 dense dummies
        rows = S.row_count(g.m_pad, g.n_nodes)
        operands = 1 + 4 + (S.ROW_WIDTH + 1) * rows * 4
    else:                      # the int8 adjacency; pull and row dummies
        operands = n_pad * n_pad + 4 + 4 + 4
    assert stats["operand_bytes"] == operands
    # 8 keys over n_pad: a byte table far below PACK_GATHER_BYTES
    assert stats["gather_words"] == 0
    assert stats["gather_table_bytes"] == n_pad * 8


def test_plan_span_carries_the_packed_gather(tmp_path, monkeypatch):
    """Past ``PACK_GATHER_BYTES`` the plan names the packed gather: one
    uint32 word a vertex for 8 keys, a table of 4 bytes a vertex."""
    monkeypatch.setattr(S, "PACK_GATHER_BYTES", 0)
    g = gen.rmat(10, 16, seed=0, directed=False)
    stats = plan_stats(g, "sparse", tmp_path)
    assert stats["gather_words"] == 1
    assert stats["gather_table_bytes"] == 4 * E.prepare_graph(g).n_pad
