"""BENCHMARK.json against the files the harness finds by name, and the
run's refusal without a chip."""
import importlib.util
import json
import pathlib
import re

import pytest

from bench import run as bench_run

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir()


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_configuration_files(conf):
    path = ROOT / conf["file"]
    assert any(conf["file"].startswith(p + "/") for p in SPEC["paths"])
    data = json.loads(path.read_text())
    assert data["name"] == conf["name"]
    # one graph per configuration: --seed draws only the traffic on it
    seed = data["graph_seed"]
    assert type(seed) is int and seed >= 0
    assert set(conf["reduced"]) <= set(data["reduced"])
    assert importlib.util.find_spec(f"bench.generators.{data['generator']}")
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cells_resolve(cell):
    _, config, traffic = bench_run.find_cell(SPEC, cell["name"])
    assert importlib.util.find_spec(f"bench.drivers.{traffic['driver']}")
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    reported = bench_run.cell_metrics(SPEC, cell["name"], trace=False)
    assert "setup_s" in [m["name"] for m in reported] and len(reported) > 1
    assert bench_run.cell_metrics(SPEC, cell["name"], trace=True)
    if traffic["driver"] == "closed_batches":
        assert traffic["batch"] == config["facade"]["source_batch"]


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_readers(metric):
    path = ROOT / "bench" / "metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location(metric["name"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    moved = e2e[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in moved.get("workloads", [cell])


def test_names_units_and_bounds():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [c["name"] for c in SPEC["configs"] + SPEC["workloads"]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_no_chip_no_result(capsys):
    rc = bench_run.main(["--workload", SPEC["workloads"][0]["name"],
                         "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "not a TPU" in out.err
