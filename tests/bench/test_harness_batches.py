"""Whole runs of the batch cells at a test's size: sound, under each
fault the cell can have, and as the control."""
import numpy as np
import pytest

from bench.drivers.closed_batches import batches
from harness_faults import FAULTS, plant, run

CELLS = ["g500_s20_bfs64", "kron_s15_apsp"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = run(cell)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"teps", "setup_s"}
    assert line["metrics"]["teps"]["value"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    plant(monkeypatch, FAULTS[fault])
    line = run(cell)
    assert not line["correct"], line["checks"]
    assert line["failed"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    # the control: sweeps capped below the depth the graph needs
    line = run(cell, options={"max_steps": 2})
    assert not line["correct"], line["checks"]


def test_a_job_is_its_keys_cut_into_batches():
    keys = np.arange(100, 200)
    job = batches(keys, 16)
    assert job.shape == (7, 16) and job.dtype == np.int32
    np.testing.assert_array_equal(job.ravel()[:100], keys)
    np.testing.assert_array_equal(job[-1, 4:], keys[:12])
    assert batches(keys, 20).shape == (5, 20)
