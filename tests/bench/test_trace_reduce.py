"""Trace reduction, on a recorded TPU v5e trace and on made-up events."""
import importlib.util
import pathlib

import pytest

from bench import trace

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
# two 64-key batches of g500_s20_bfs64 on one v5e chip, profiled
RECORDED = BENCH / "testdata" / "g500_s20_two_batches.xplane.pb.gz"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(RECORDED))


def test_recorded_window_and_busy(recorded):
    assert recorded.window_s == pytest.approx(15.0375, abs=1e-3)
    assert recorded.busy_s() == pytest.approx(15.0224, abs=1e-3)
    assert len(recorded.busy) == 1
    assert len(recorded.named("apsp")) == 2


def test_recorded_breakdown_counts_leaf_ops_once(recorded):
    # the while loop holds the sweep's fusions on the same line
    assert not any(k.startswith("while") for k in recorded.op_seconds)
    assert sum(recorded.op_seconds.values()) == \
        pytest.approx(recorded.busy_s(), rel=1e-3)
    ops = recorded.breakdown()["device_ops"]
    assert ops[0][0] == "fusion.26 s8[31457280,64]"
    assert ops[0][1] == pytest.approx(7.216, abs=1e-3)
    assert len(ops) == trace.TOP


def test_recorded_metrics(recorded):
    counters = {"sweeps": [7, 7]}
    assert reader("device_idle.bfs")(recorded, counters) == \
        pytest.approx(0.1008, abs=1e-3)
    assert reader("sweep_device_ms.bfs")(recorded, counters) == \
        pytest.approx(1073.03, abs=0.1)
    assert reader("host_gap_ms.bfs")(recorded, counters) == \
        pytest.approx(5.43, abs=0.01)


def made_up():
    ops = [("%while = x", 1.0, 5.0), ("%a = s8[4]{0} f()", 1.0, 2.0),
           ("%b = s8[4]{0} f()", 2.5, 4.0), ("%c = s8[4]{0} f()", 7.0, 8.0),
           ("%a = s8[4]{0} f()", 8.5, 12.0)]
    spans = [("window", 0.5, 10.0), ("apsp", 0.6, 4.2), ("tick", 4.5, 9.0),
             ("submit", 6.0, 6.5)]
    return trace.from_events([ops], spans)


def test_made_up_busy_idle_and_labels():
    t = made_up()
    assert t.window_s == pytest.approx(9.5)
    # busy: while [1, 5] and c [7, 8] and a clipped [8.5, 10]
    assert t.busy_s() == pytest.approx(4.0 + 1.0 + 1.5)
    assert t.busy_s(0.0, 3.0) == pytest.approx(2.0)
    assert t.op_seconds == pytest.approx(
        {"a s8[4]": 2.5, "b s8[4]": 1.5, "c s8[4]": 1.0})
    gaps = t.idle_gaps()
    assert [g[0] for g in gaps] == ["submit", "apsp", "tick"]
    assert [g[1] for g in gaps] == pytest.approx([2.0, 0.5, 0.5])


def test_two_devices_average():
    one = [("%a = f32[] f()", 0.0, 2.0)]
    two = [("%a = f32[] f()", 0.0, 1.0)]
    t = trace.from_events([one, two], [("window", 0.0, 4.0)])
    assert t.busy_s() == pytest.approx(1.5)


def test_malformed_traces_are_refused():
    with pytest.raises(ValueError, match="window"):
        trace.from_events([[("%a = f32[] f()", 0.0, 1.0)]], [])
    with pytest.raises(ValueError, match="device"):
        trace.from_events([], [("window", 0.0, 1.0)])


@pytest.mark.parametrize("name,counters,want", [
    ("sparse_sweep_share.bfs", {"direction_counts": [1, 1, 6]}, 75.0),
    ("sparse_sweep_share.bfs", {"direction_counts": [0, 0, 0]}, None),
    ("tier_hit_share.p2p", {"tiers": {"cache": 2, "oracle": 1,
                                      "sweep": 5}}, 37.5),
    ("flush_ms.p2p", {"flush_s": [0.1, 0.3]}, 200.0),
    ("flush_ms.p2p", {"flush_s": []}, None),
    ("gen_lag_p95_ms.p2p", {"lag_s": [0.001] * 19 + [1.0]}, 50.95),
    ("sweeps_per_call.bfs", {"sweeps": [7, 8, 7, 7]}, 7.25),
    ("sweeps_per_call.bfs", {"sweeps": []}, None),
])
def test_counter_readers(name, counters, want):
    got = reader(name)(made_up(), counters)
    assert got == (None if want is None else pytest.approx(want))
