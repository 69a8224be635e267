"""Shared timing/acceptance machinery for the fixed-vs-auto JSON
benchmarks (bench_apsp boolean engine, bench_weighted tropical engine)."""
from __future__ import annotations

import statistics
import time
from typing import Callable, Dict

TOLERANCE = 1.25       # auto vs best fixed: timing-noise allowance (when
                       # auto pins the best direction it runs the *same*
                       # sweeps, so any gap is wall-clock jitter — observed
                       # up to ~20% on shared CI boxes even best-of-10)
BEAT_MARGIN = 1.25     # auto vs worse fixed: require a real win


def time_interleaved_stats(fns: Dict[str, Callable], repeats: int
                           ) -> Dict[str, Dict[str, float]]:
    """Per-mode ``{"best": min, "median": median}`` over ``repeats``
    rounds, modes interleaved within each round so machine-load drift
    hits all modes equally.  ``best`` drives the fixed-vs-auto acceptance
    booleans (least-noise estimator); ``median`` is what the CI
    regression gate compares run-over-run (robust to a single slow
    round)."""
    for fn in fns.values():
        fn()  # warmup: jit compile + device transfer
    samples: Dict[str, list] = {k: [] for k in fns}
    for _ in range(repeats):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            samples[k].append(time.perf_counter() - t0)
    return {k: {"best": min(v), "median": statistics.median(v)}
            for k, v in samples.items()}


def auto_vs_fixed(row: Dict, fixed_modes) -> None:
    """Fill the acceptance fields of one family row in place, given
    ``t_auto`` and ``t_<mode>`` timings already present."""
    best = min(row[f"t_{m}"] for m in fixed_modes)
    worse = max(row[f"t_{m}"] for m in fixed_modes)
    row["auto_vs_best"] = row["t_auto"] / best
    row["auto_vs_worse"] = row["t_auto"] / worse
    row["auto_no_slower_than_best"] = row["auto_vs_best"] <= TOLERANCE
    row["auto_beats_worse"] = worse / row["t_auto"] >= BEAT_MARGIN
