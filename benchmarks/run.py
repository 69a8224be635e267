"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--out BENCH_RESULTS.json]

Prints ``name,us_per_call,derived`` CSV rows and writes a machine-readable
aggregate (default ``BENCH_RESULTS.json``; ``--out ''`` disables) so the
perf trajectory can be tracked run-over-run and uploaded as a CI artifact.
All benchmarks are seeded — two runs on the same machine measure the same
work:

  * bench_sssp        — Tables 7/8 (speedup over GAP-standin / queue BFS)
  * bench_scaling     — Tables 5/6 + Figs 3/4 (batch-parallel efficiency)
  * bench_memory      — §3.4 / Eq. 13 memory model
  * bench_complexity  — Eqs. 5/6/10 work-bound verification
  * bench_batching    — beyond-paper: blocked multi-source GEMM + tile-skip
                        (JSON; tile_skip_fraction rides the hard gate)
  * bench_serving     — serving tier: open-loop Poisson load against the
                        tiered GraphService (row cache -> landmark oracle
                        -> bucketed sweeps); p50/p99/QPS advisory,
                        hit-rate / certified-fraction / labels checksum
                        hard-gated, bit-identity asserted in-bench (JSON)
  * bench_weighted    — paper §5 extension through the tropical engine:
                        fixed-dense vs fixed-sparse vs auto (JSON) + scipy
                        Dijkstra baseline
  * bench_apsp        — direction-optimized batched APSP engine:
                        fixed-push vs fixed-pull vs auto (JSON)
  * bench_sharded     — semiring-generic sharded executor vs the fixed
                        single-device engine (bit-identical asserted,
                        collective overhead measured; JSON)
  * bench_centrality  — counting-semiring analytics bundle: NumPy
                        per-source loop vs jit-batched vs Pallas kernel
                        (betweenness asserted equal, sigma checksum
                        recorded for the hard gate; JSON)
  * bench_dynamic     — streaming tier: locality-heavy interleaved
                        update/query stream over DynamicCSRGraph;
                        frontier-seeded repair vs scratch recompute
                        (bit-identity and repair_sweeps < scratch_sweeps
                        asserted in-bench; sweep totals, epoch counters
                        and query checksum hard-gated; JSON)
  * bench_resume      — resumable-job layer: checkpointed counting-APSP
                        job vs kill-at-half + resume (bit-identity
                        asserted in-bench; dist/sigma checksums and the
                        resumed-chunk accounting hard-gated; JSON)
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import sys
import time

import jax

from repro.launch.compile_cache import enable_compile_cache

from . import (bench_apsp, bench_batching, bench_centrality,
               bench_complexity, bench_dynamic, bench_memory, bench_resume,
               bench_scaling, bench_serving, bench_sharded, bench_sssp,
               bench_weighted, regression)


def _csv_rows_to_records(rows):
    records = []
    for row in rows[1:]:                      # skip the header
        name, us, derived = row.split(",", 2)
        # derived-only rows (memory model, work-bound checks) carry no time
        records.append({"name": name,
                        "us_per_call": float(us) if us else None,
                        "derived": derived})
    return records


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", type=str, default="BENCH_RESULTS.json",
                    help="aggregate JSON path ('' to disable)")
    ap.add_argument("--check-against", type=str, default=None,
                    metavar="BASELINE.json",
                    help="regression gate: compare this run against a "
                         "committed baseline aggregate and exit non-zero "
                         "on hard regressions (see benchmarks/regression.py)")
    args = ap.parse_args()
    enable_compile_cache(pathlib.Path(__file__).resolve().parents[1])

    rows = ["name,us_per_call,derived"]
    t0 = time.time()
    bench_sssp.run(n_sources=4 if args.quick else 16, csv=rows)
    bench_scaling.run(csv=rows)
    bench_memory.run(csv=rows)
    bench_complexity.run(csv=rows, n_sources=4 if args.quick else 8)
    batching = bench_batching.run(quick=args.quick,
                                  repeats=2 if args.quick else 3, csv=rows)
    serving = bench_serving.run(quick=args.quick,
                                n_queries=20_000 if args.quick else 100_000,
                                csv=rows)
    weighted = bench_weighted.run(quick=args.quick,
                                  repeats=2 if args.quick else 5, csv=rows)
    apsp = bench_apsp.run(quick=args.quick,
                          repeats=3 if args.quick else 10, csv=rows)
    sharded = bench_sharded.run(quick=args.quick,
                                repeats=2 if args.quick else 5, csv=rows)
    central = bench_centrality.run(quick=args.quick,
                                   repeats=2 if args.quick else 3,
                                   csv=rows)
    dynamic = bench_dynamic.run(quick=args.quick,
                                repeats=2 if args.quick else 3, csv=rows)
    resume = bench_resume.run(quick=args.quick,
                              repeats=2 if args.quick else 3, csv=rows)
    total = time.time() - t0
    print("\n".join(rows))
    print(f"# total {total:.1f}s", file=sys.stderr)

    aggregate = {
        "schema": 2,
        "quick": args.quick,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "total_seconds": total,
        "gate": {"time_tol": regression.DEFAULT_TIME_TOL,
                 "min_gate_seconds": regression.MIN_GATE_SECONDS},
        "rows": _csv_rows_to_records(rows),
        "bench_apsp": apsp,
        "bench_weighted": weighted,
        "bench_sharded": sharded,
        "bench_centrality": central,
        "bench_batching": batching,
        "bench_serving": serving,
        "bench_dynamic": dynamic,
        "bench_resume": resume,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(aggregate, f, indent=2)
            f.write("\n")
        print(f"# aggregate written to {args.out}", file=sys.stderr)
    if args.check_against:
        if regression.check_against(aggregate, args.check_against):
            sys.exit(1)


if __name__ == "__main__":
    main()
