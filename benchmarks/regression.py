"""Benchmark regression gate: compare a fresh BENCH_RESULTS aggregate
against a committed baseline (``BENCH_BASELINE.json``) and fail CI when
something real regressed.

Two classes of checks, calibrated to what is and is not deterministic:

  * **hard gates** — fields that are exact given the seeds: the set of
    benchmark rows (nothing silently dropped), per-family graph shapes
    (``n_nodes``/``n_edges``), **sweep counts** (the Fact-1 iteration
    counts) and the counting semiring's **sigma checksum** (the sum of
    shortest-path counts — exact integers in f32; any change means the
    algorithm did different work, not that the machine was slow), and the
    serving tier's determinism fields (landmark ``labels_checksum``,
    oracle ``certified_count``/``certified_fraction``, load-loop
    ``hit_rate`` and tier hit counters — the gated load run flushes on
    size thresholds over a virtual clock, so these are pure functions of
    the seeds).  A mismatch always fails.
  * **timing gates** — per-family interleaved best-of-N *medians*
    (``t_<mode>_median`` from ``_timing.time_interleaved_stats``).  Wall
    clock is ±30% noisy on shared runners and the baseline may have been
    recorded on different hardware, so the threshold is generous
    (``time_tol``, stored in the baseline's ``gate`` block) and timings
    under ``min_gate_seconds`` are ignored entirely.

The acceptance booleans (``auto_no_slower_than_best`` etc.) are
themselves timing-derived, so they warn rather than fail.

    PYTHONPATH=src python -m benchmarks.run --quick \
        --check-against benchmarks/BENCH_BASELINE.json
"""
from __future__ import annotations

import json
from typing import Dict, List, Tuple

# The baseline may have been recorded on different hardware than the CI
# runner and --quick medians come from 2-3 samples, so both knobs are
# deliberately loose: the timing gate exists to catch order-of-magnitude
# regressions (an accidental O(n^2) hot path, a dropped jit), not single-
# digit-percent drift — that's what the hard sweep-count gates and the
# uploaded aggregates are for.
DEFAULT_TIME_TOL = 6.0        # median may grow this much before failing
MIN_GATE_SECONDS = 5e-3       # ignore timings too small to be stable

_HARD_FAMILY_FIELDS = ("n_nodes", "n_edges", "n_sources", "sweeps",
                       "sweeps_fused", "sweeps_tropical", "sigma_checksum",
                       # serving tier: all pure functions of graph +
                       # landmarks + seeded arrival order (the gated load
                       # loop runs on a virtual clock with size-threshold-
                       # only flushing, so no wall-clock dependence)
                       "n_queries", "n_landmarks", "labels_checksum",
                       "certified_count", "certified_fraction", "hit_rate",
                       "cache_hits", "oracle_hits", "sweep_served",
                       # kernel tile occupancy: graph + schedule only
                       "tile_skip_fraction",
                       # dynamic tier: the recorded update stream is
                       # seeded, so repair/scratch sweep totals, the
                       # bit-identity flag, the epoch/compaction
                       # counters and the interleaved-query checksum
                       # are exact
                       "repair_sweeps", "scratch_sweeps",
                       "repair_equals_scratch", "n_epochs",
                       "n_compactions", "query_checksum",
                       # resumable jobs: full-run checksums and the
                       # resumed-chunk accounting are exact given the
                       # seeds (bit-identity full-vs-resumed is asserted
                       # in-bench before the JSON is written)
                       "chunks_total", "dist_checksum",
                       "checkpoints_written", "resumed_chunks",
                       "recomputed_chunks", "resume_equals_full",
                       # autotuner: the static roofline plan is a pure
                       # function of graph shape + backend profile, so
                       # its checksum changing means the tuner decided
                       # differently (tiles / fused gate / cost
                       # constants), never that the machine was slow
                       "tuning_plan_checksum")
_BENCHES = ("bench_apsp", "bench_weighted", "bench_sharded",
            "bench_centrality", "bench_batching", "bench_serving",
            "bench_dynamic", "bench_resume")


def load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def compare(current: Dict, baseline: Dict
            ) -> Tuple[List[str], List[str]]:
    """Returns (failures, warnings).  Empty failures == gate passes."""
    failures: List[str] = []
    warnings: List[str] = []
    gate = baseline.get("gate", {})
    time_tol = float(gate.get("time_tol", DEFAULT_TIME_TOL))
    min_gate = float(gate.get("min_gate_seconds", MIN_GATE_SECONDS))

    # -- structural: every baseline CSV row still exists -------------------
    cur_rows = {r["name"] for r in current.get("rows", [])}
    for r in baseline.get("rows", []):
        if r["name"] not in cur_rows:
            failures.append(f"row {r['name']!r} present in baseline but "
                            f"missing from this run")

    for bench in _BENCHES:
        base_b = baseline.get(bench) or {}
        cur_b = current.get(bench) or {}
        for fam, brow in base_b.get("families", {}).items():
            crow = cur_b.get("families", {}).get(fam)
            if crow is None:
                failures.append(f"{bench}/{fam}: family missing")
                continue
            # hard: deterministic-by-seed fields
            for field in _HARD_FAMILY_FIELDS:
                if field in brow and crow.get(field) != brow[field]:
                    failures.append(
                        f"{bench}/{fam}: {field} changed "
                        f"{brow[field]} -> {crow.get(field)} "
                        f"(deterministic field; the algorithm did "
                        f"different work)")
            # timing: interleaved medians, generous tolerance
            for key, bval in brow.items():
                if not key.endswith("_median"):
                    continue
                cval = crow.get(key)
                if cval is None:
                    failures.append(f"{bench}/{fam}: {key} missing")
                    continue
                if cval < min_gate:
                    continue
                # floor the baseline so a sub-millisecond baseline can't
                # hide an unbounded regression (tiny/tiny stays exempt
                # via the cval check above)
                ratio = cval / max(bval, min_gate)
                if ratio > time_tol:
                    failures.append(
                        f"{bench}/{fam}: {key} regressed {ratio:.2f}x "
                        f"({bval * 1e3:.2f} ms -> {cval * 1e3:.2f} ms, "
                        f"tol {time_tol}x)")
                elif ratio > 0.5 * time_tol + 0.5:
                    warnings.append(
                        f"{bench}/{fam}: {key} drifted {ratio:.2f}x "
                        f"(under the {time_tol}x gate)")
            # advisory: timing-derived acceptance booleans (the two
            # bit-identity flags are asserted in-bench before the JSON is
            # written; a flip here means a hand-edited aggregate)
            for flag in ("auto_no_slower_than_best", "auto_beats_worse",
                         "fused_equals_per_sweep",
                         "packed_push_matches_f32",
                         "oracle_p50_beats_exact",
                         "autotuned_beats_default"):
                if brow.get(flag) and not crow.get(flag, True):
                    warnings.append(f"{bench}/{fam}: {flag} flipped "
                                    f"True -> False (timing-derived; "
                                    f"not gated)")
    return failures, warnings


def check_against(current: Dict, baseline_path: str) -> int:
    """Print a report; return the number of hard failures."""
    baseline = load(baseline_path)
    failures, warnings = compare(current, baseline)
    for w in warnings:
        print(f"[bench-gate] WARN {w}")
    for f in failures:
        print(f"[bench-gate] FAIL {f}")
    if failures:
        print(f"[bench-gate] {len(failures)} regression(s) vs "
              f"{baseline_path}")
    else:
        print(f"[bench-gate] OK — no regressions vs {baseline_path} "
              f"({len(warnings)} warning(s))")
    return len(failures)
