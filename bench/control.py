"""Read a cell's compared numbers over many seeds in one process, for the
program as configured or for the control.

    python3 -m bench.control --workload g500_s20_bfs64 --seeds 1,2,3 --seconds 8 [--max-steps 4]

The control is the program's own option that breaks the configuration's
guarantee of exact distances: ``max_steps`` caps the sweeps a search may
run, so vertices farther than the cap stay unreached.  Each seed runs
the cell's driver as ``bench.run`` does (its own traffic on the
configuration's one graph, warm-up, a window of ``--seconds`` at the
cell's own load, the comparison) and prints one JSON line with the
compared numbers and ``correct``.  The limits in ``PERF.md`` were set
from these readings; the benchmark's runs do not call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run as bench_run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--max-steps", type=int, default=None)
    args = ap.parse_args(argv)

    try:
        _, _, config, traffic, _ = bench_run.start(args.workload)
    except bench_run.NoChip as e:
        print(f"bench.control: {e}", file=sys.stderr)
        return 2

    from bench.harness import Context
    options = {} if args.max_steps is None else \
        {"max_steps": args.max_steps}
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = Context(config=config, traffic=traffic, seed=seed,
                      seconds=args.seconds, trace=False,
                      t_start=time.perf_counter(), options=options)
        out = bench_run.execute(ctx, traffic["driver"])
        print(json.dumps({
            "seed": seed, "options": options,
            "correct": all(v <= lim for v, lim in out.checks.values()),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": out.metrics,
            "sweeps_per_call": sorted(set(out.counters.get("sweeps", []))),
            "checks": {k: v for k, (v, _) in out.checks.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
