"""What the program's own names leave in a trace (bench/program_trace.py),
on recorded TPU v5e traces and on made-up events."""
import gzip
import json
import pathlib

import pytest

from bench import program_trace as pt_mod

TESTDATA = pathlib.Path(__file__).resolve().parents[2] / "bench" / "testdata"
# two 64-key batches of g500_s20_bfs64 on one v5e chip, before the
# program named anything
G500 = TESTDATA / "g500_s20_two_batches.xplane.pb.gz"
# one traced second of kron_s15_p2p on one v5e chip, with the dawn.* names
# (bench.run --trace 1 --seconds 1 --keep-trace)
P2P = TESTDATA / "kron_s15_p2p_dawn.xplane.pb.gz"


@pytest.fixture(scope="module")
def g500():
    return pt_mod.load(str(G500))


@pytest.fixture(scope="module")
def p2p():
    return pt_mod.load(str(P2P))


def test_tf_op_of_the_sweep_ops():
    tf = pt_mod.device_tf_ops(gzip.open(G500).read())
    by_op = {name.split(" = ")[0]: op for name, op in tf.items()}
    assert by_op["%fusion.26"] == "jit(_run_batch)/while/body/gather"
    assert by_op["%fusion.27"] == "jit(_run_batch)/while/body/scatter-max"
    assert by_op["%sort.2"].startswith("jit(_run_batch)/while/body/")


def test_launches_inside_each_benchmark_span(g500):
    spans = g500.base.named("apsp")
    assert len(spans) == 2
    for s, e in spans:
        assert g500.launches_in(s, e, "python3") == \
            {"execute": 14, "device_put": 8}


def test_idle_labels_unchanged_without_program_spans(g500):
    assert g500.program_spans == []
    assert g500.idle_gaps() == g500.base.idle_gaps()


def test_device_time_by_program(g500):
    scopes = g500.scope_seconds()
    assert scopes["jit(_run_batch)"] == pytest.approx(15.0168, abs=1e-3)
    assert sum(scopes.values()) == pytest.approx(g500.base.busy_s(),
                                                 rel=1e-3)
    assert g500.scope_s("sweep.sparse") == 0.0


def made_up():
    tf_sparse = "jit(_run_batch)/while/body/dawn.sweep.sparse/gather"
    tf_choose = "jit(_run_batch)/while/body/dawn.sweep.choose/reduce"
    ops = [("%while = x", 1.0, 5.0, "jit(_run_batch)/while"),
           ("%a = s8[4]{0} f()", 1.0, 2.0, tf_choose),
           ("%b = s8[4]{0} f()", 2.5, 4.0, tf_sparse),
           ("%c = s8[4]{0} f()", 7.0, 8.0, tf_sparse),
           ("%d = s8[4]{0} f()", 8.5, 12.0, None)]
    bench_spans = [("window", 0.5, 10.0), ("apsp", 0.6, 4.2),
                   ("tick", 4.5, 9.6), ("wait", 8.1, 8.4)]
    program = [
        ("dawn.apsp", 0.7, 1.5, {"semiring": "boolean", "n_sources": 4},
         "py"),
        ("dawn.engine.tile", 0.72, 1.2, {"valid": 4, "tile": 8}, "py"),
        ("dawn.serve.submit", 4.6, 4.7, {}, "py"),
        ("dawn.serve.submit", 4.8, 5.0, {}, "py"),
        ("dawn.serve.flush", 5.5, 8.5,
         {"rows": 3, "tile": 8, "wait_ms": 30.0}, "py"),
        ("dawn.serve.flush", 8.6, 9.5,
         {"rows": 5, "tile": 8, "wait_ms": 10.0}, "py"),
        ("dawn.serve.flush.wait", 5.6, 6.5, {}, "py")]
    launches = [("execute", 0.9, 0.95, "py"), ("device_put", 0.85, 0.86,
                                               "py"),
                ("execute", 0.9, 0.95, "other"),
                ("execute", 1.6, 1.7, "py")]
    return pt_mod.from_events([ops], bench_spans, program, launches)


def test_made_up_labels_take_the_innermost_span_of_either_kind():
    t = made_up()
    # gaps [0.5, 1.0], [5.0, 7.0] and [8.0, 8.5], labelled at 0.75, 6.0
    # and 8.25
    gaps = t.idle_gaps()
    assert [g[0] for g in gaps] == \
        ["dawn.serve.flush.wait", "dawn.engine.tile", "wait"]
    assert [g[1] for g in gaps] == pytest.approx([2.0, 0.5, 0.5])
    assert [g[0] for g in t.base.idle_gaps()] == ["tick", "apsp", "wait"]


def test_made_up_spans_args_and_scopes():
    t = made_up()
    top, = t.spans("apsp")
    assert top.args == {"semiring": "boolean", "n_sources": 4}
    assert top.thread == "py"
    assert t.launches_in(top.start, top.end, top.thread) == \
        {"execute": 1, "device_put": 1}
    assert t.scope_s("sweep.sparse") == pytest.approx(2.5)
    assert t.scope_s("sweep.choose") == pytest.approx(1.0)
    assert t.scope_seconds() == pytest.approx(
        {"dawn.sweep.choose": 1.0, "dawn.sweep.sparse": 2.5, "-": 1.5})
    summary = t.breakdown()["program_spans"]
    assert summary["dawn.serve.submit"] == {"count": 2,
                                            "mean_ms": pytest.approx(150.0)}


@pytest.mark.parametrize("name,counters,want", [
    ("apsp_host_ms.bfs", {}, 800.0),
    ("host_launches.bfs", {}, 2.0),
    ("sparse_form_ms.bfs", {"direction_counts": [0, 0, 5]}, 500.0),
    ("sparse_form_ms.bfs", {"direction_counts": [3, 0, 0]}, None),
    ("sparse_form_ms.bfs", {}, None),
    ("choose_share.bfs", {}, 100.0 * 1.0 / 6.5),
    ("admit_us.p2p", {}, 150000.0),
    # flush [5.5, 8.5] holds 1.0 s busy; [8.6, 9.5] holds 0.9 s
    ("flush_host_ms.p2p", {}, 1e3 * (2.0 + 0.0) / 2),
    ("flush_fill.p2p", {}, 50.0),
    ("queue_wait_ms.p2p", {}, 20.0),
])
def test_readers_on_made_up_events(name, counters, want):
    got = pt_mod.READERS[name](made_up(), counters)
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("name", sorted(pt_mod.READERS))
def test_readers_find_nothing_without_the_names(name, g500):
    counters = {"sweeps": [7, 7], "direction_counts": [0, 0, 14]}
    assert pt_mod.READERS[name](g500, counters) is None


@pytest.mark.parametrize("name,want", [
    ("admit_us.p2p", 27.21999),
    ("flush_host_ms.p2p", 4.91010),
    ("flush_fill.p2p", 28.19602),
    ("queue_wait_ms.p2p", 77.07442),
])
def test_recorded_p2p_readings(p2p, name, want):
    assert pt_mod.READERS[name](p2p, {}) == pytest.approx(want, abs=1e-4)


def test_recorded_p2p_names(p2p):
    assert p2p.scope_s("sweep.sparse") == pytest.approx(1.14437, abs=1e-5)
    flushes = p2p.spans("serve.flush")
    assert len(flushes) == 11
    assert all(sp.args["rows"] <= sp.args["tile"] == 128 for sp in flushes)
    parts = {sp.name for sp in p2p.program_spans
             if sp.name.startswith("dawn.serve.flush.")}
    assert parts == {"dawn.serve.flush.wait", "dawn.serve.flush.copy",
                     "dawn.serve.flush.fill"}
    labels = [g[0] for g in p2p.idle_gaps() if g[1] >= 1e-4]
    assert labels and not {"tick", "idle"} & set(labels)


def test_reduce_a_kept_trace_from_the_command_line(capsys):
    assert pt_mod.main(["--file", str(P2P)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["metrics"]["flush_fill.p2p"] == pytest.approx(28.19602,
                                                             abs=1e-4)
    assert out["breakdown"]["device_scopes"][0][0] == "dawn.sweep.sparse"
