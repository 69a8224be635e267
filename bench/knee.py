"""Sweep the offered rate of an open-loop cell to find its knee.

    python3 -m bench.knee --workload kron_s15_p2p --seed 7 --rates 50,100,200 --seconds 10

One process sets the cell up once, on the configuration's one graph (its
``graph_seed``), then runs one window per rate, in the order given, each
with its own arrivals drawn from ``--seed``.  Per rate it prints one JSON
line: the queries due, the backlog (due but unanswered) at each quarter
of the window, the latency median and 95th percentile, and the median
flush time.  The knee is the highest rate whose backlog does not grow
over the window; the cell runs at four fifths of it.  Used once, to set
``rate`` and ``limit_s`` in the traffic file; the benchmark's runs do
not call it.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench import run as bench_run


def backlog(served, t0: float, seconds: float):
    """Queries due but unanswered at each quarter of the window."""
    out = []
    for f in (0.25, 0.5, 0.75, 1.0):
        t = t0 + f * seconds
        due = int((served.due <= t).sum())
        done = int((np.nan_to_num(served.done, nan=np.inf) <= t).sum())
        out.append(due - done)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    try:
        _, _, config, traffic, _ = bench_run.start(args.workload)
    except bench_run.NoChip as e:
        print(f"bench.knee: {e}", file=sys.stderr)
        return 2

    from bench.drivers import open_loop
    from bench.harness import STREAM_ARRIVALS, Context
    ctx = Context(config=config, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=False, t_start=0.0)
    svc, _, order = open_loop.setup(ctx)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        s = open_loop.window(svc, ctx.rng(STREAM_ARRIVALS + 10 * (i + 1)),
                             order, {**traffic, "rate": rate, "drain_s": 10},
                             args.seconds, ctx.spans)
        lat = (s.done - s.due)[~np.isnan(s.done)]
        print(json.dumps({
            "rate": rate, "due": len(s.due),
            "backlog_quarters": backlog(s, s.start, args.seconds),
            "latency_p50_ms": 1e3 * float(np.median(lat)),
            "latency_p95_ms": 1e3 * float(np.percentile(lat, 95)),
            "flush_p50_ms": 1e3 * float(np.median(s.flush_s))
            if s.flush_s else None,
            "flushes": len(s.flush_s),
            "unanswered": int(np.isnan(s.done).sum())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
