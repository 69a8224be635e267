"""Whole runs of the serving cell at a test's size: sound, under each
fault the cell can have, and as the control."""
import numpy as np
import pytest

from bench.drivers.open_loop import schedule
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


def test_every_seed_offers_the_same_load():
    order = np.arange(100, 200)
    windows = [schedule(np.random.default_rng(seed), order, 560.0, 4.0, 1.0)
               for seed in (1, 2**31 + 11)]
    for at, src, dst in windows:
        assert len(at) == len(src) == len(dst) == 2240
        assert np.all(np.diff(at) >= 0) and 0.0 <= at[0] and at[-1] < 4.0
        assert np.isin(src, order).all() and np.isin(dst, order).all()
    assert not np.array_equal(windows[0][0], windows[1][0])
