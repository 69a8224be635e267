"""Whole runs of the serving cell at a test's size: sound, under each
fault the cell can have, and as the control."""
import pytest

from harness_faults import FAULTS, plant, run

CELL = "kron_s15_p2p"


def test_sound_run_is_correct():
    line = run(CELL, seconds=1.0)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 50
    assert line["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"query_p95_ms", "goodput_qps",
                                    "setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    plant(monkeypatch, FAULTS[fault])
    line = run(CELL, seconds=1.0)
    assert not line["correct"], line["checks"]


def test_control_is_not_correct():
    line = run(CELL, seconds=1.0, options={"max_steps": 2})
    assert not line["correct"], line["checks"]
