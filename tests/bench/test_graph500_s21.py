"""The Graph500 scale-21 deployment: its configuration keeps the spec's
shape, its static shapes stay on the row program, and its cell runs
sound at a test's size."""
import json

from harness_faults import ROOT, SPEC, run
from repro.core import sweep as S

CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in SPEC["configs"]}
S21 = CONFIGS["graph500_s21"]
S20 = CONFIGS["graph500_s20"]


def test_sound_run_is_correct():
    line = run("g500_s21_bfs64")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"teps", "setup_s"}
    assert line["metrics"]["teps"]["value"] > 0


def test_configuration_keeps_the_specs_shape():
    graph = S21["graph"]
    assert (graph["a"], graph["b"], graph["c"]) == (0.57, 0.19, 0.19)
    assert graph["edge_factor"] == 16 and graph["scale"] == 21
    assert S21["graph_seed"] == 1 and S21["generator"] == "kronecker"
    assert S21["facade"] == {"mode": "sparse", "source_batch": 64}
    cell, = [w for w in SPEC["workloads"] if w["config"] == "graph500_s21"]
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert traffic["batch"] == 64 and cell["chips"] == 1
    # the scale-20 deployment but for its scale, m_pad and texts
    texts = ("name", "source", "reduced", "assumed")
    assert {k: v for k, v in S21.items() if k not in texts + ("graph",)} \
        == {k: v for k, v in S20.items() if k not in texts + ("graph",)}
    assert set(S21) == set(S20)
    assert {k: v for k, v in graph.items() if k not in ("scale", "m_pad")} \
        == {k: v for k, v in S20["graph"].items()
            if k not in ("scale", "m_pad")}


def test_static_shapes_take_rows_of_eight():
    graph = S21["graph"]
    m_pad, n = graph["m_pad"], 1 << graph["scale"]
    assert m_pad % (1 << 20) == 0
    # at most every generated edge in both directions
    assert m_pad <= 2 * graph["edge_factor"] * n
    assert S.row_width(m_pad, n) == S.ROW_WIDTH == 8
    # the gather's slot count indexes in int32
    assert 8 * S.row_count(m_pad, n) < 2 ** 31
