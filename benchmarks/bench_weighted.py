"""Paper §5 extension: the tropical (min,+) engine — fixed-dense vs
fixed-sparse vs auto, plus the scipy-Dijkstra external baseline.

Mirror of ``bench_apsp``: one source tile through the
``core/weighted.py::weighted_apsp`` driver on each family with the form
pinned to dense, pinned to sparse, and chosen per sweep by the engine's
occupancy cost model, emitting a JSON document with per-family timings
and two advisory booleans:

  * ``auto_no_slower_than_best_everywhere`` — auto within TOLERANCE of
    min(dense, sparse) on every family;
  * ``auto_beats_worse_on`` — families where auto beats the *worse* fixed
    form by a real margin (>= 1.25x).

    PYTHONPATH=src python -m benchmarks.bench_weighted [--quick] [--out f.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from repro.core import (WeightedConfig, dijkstra_oracle, minplus_sssp,
                        prepare_weighted)
from repro.core.weighted import weighted_apsp
from repro.graph import generators as gen

from ._timing import (BEAT_MARGIN, TOLERANCE, auto_vs_fixed,
                      time_interleaved_stats)

FAMILIES: Dict[str, Callable] = {
    "grid_road": lambda: gen.grid2d(32, 32),
    "rmat_social": lambda: gen.rmat(10, 8, directed=False, seed=1),
    "ws_citation": lambda: gen.watts_strogatz(1024, 8, 0.05, seed=3),
    "mycielskian": lambda: gen.mycielskian(9),
}

QUICK_FAMILIES = ("grid_road", "mycielskian")

_MODES = ("dense", "sparse", "auto")


def run(quick: bool = False, n_sources: int = 32, repeats: int = 5,
        csv: Optional[List[str]] = None) -> Dict:
    rng = np.random.default_rng(0)
    names = QUICK_FAMILIES if quick else tuple(FAMILIES)
    families = {}
    beats_worse = []
    auto_ok_everywhere = True
    for name in names:
        g = FAMILIES[name]()
        w = rng.uniform(0.5, 4.0, g.m_pad).astype(np.float32)
        pw = prepare_weighted(g, w)
        sources = np.arange(min(n_sources, g.n_nodes), dtype=np.int32)
        row: Dict = {"n_nodes": g.n_nodes, "n_edges": g.n_edges,
                     "n_sources": int(len(sources))}

        last_auto: List = []

        def make_go(mode):
            cfg = WeightedConfig(mode=mode, source_batch=32)

            def go():
                res = weighted_apsp(pw, sources=sources, config=cfg)
                res.dist.block_until_ready()
                if mode == "auto":
                    last_auto[:] = [res]
            return go

        stats = time_interleaved_stats({m: make_go(m) for m in _MODES},
                                       repeats)
        for mode, st in stats.items():
            row[f"t_{mode}"] = st["best"]
            row[f"t_{mode}_median"] = st["median"]
        res = last_auto[0]
        row["sweeps"] = int(res.sweeps)
        row["auto_direction_counts"] = dict(
            zip(("dense", "sparse"),
                np.asarray(res.direction_counts).tolist()))
        auto_vs_fixed(row, ("dense", "sparse"))
        auto_ok_everywhere &= row["auto_no_slower_than_best"]
        if row["auto_beats_worse"]:
            beats_worse.append(name)

        # external baseline: scipy Dijkstra (compiled C) per source, and
        # the single-source minplus path (the non-batched API)
        srcs_dij = sources[: min(4, len(sources))]
        t0 = time.perf_counter()
        for s in srcs_dij:
            dijkstra_oracle(g, w, int(s))
        row["t_scipy_dijkstra_per_source"] = \
            (time.perf_counter() - t0) / len(srcs_dij)
        import jax.numpy as jnp
        wj = jnp.asarray(w)
        minplus_sssp(g, wj, 0).dist.block_until_ready()  # jit
        t0 = time.perf_counter()
        for s in srcs_dij:
            minplus_sssp(g, wj, int(s)).dist.block_until_ready()
        row["t_minplus_sssp_per_source"] = \
            (time.perf_counter() - t0) / len(srcs_dij)

        families[name] = row
        if csv is not None:
            csv.append(f"weighted_{name},{row['t_auto'] * 1e6:.1f},"
                       f"auto_vs_best={row['auto_vs_best']:.2f}")
    return {
        "benchmark": "bench_weighted",
        "tolerance": TOLERANCE,
        "beat_margin": BEAT_MARGIN,
        "families": families,
        "auto_no_slower_than_best_everywhere": auto_ok_everywhere,
        "auto_beats_worse_on": beats_worse,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--sources", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()
    result = run(quick=args.quick, n_sources=args.sources,
                 repeats=args.repeats)
    text = json.dumps(result, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
