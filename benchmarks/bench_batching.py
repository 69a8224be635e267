"""Beyond-paper: multi-source blocked GEMM vs per-source sweeps (DESIGN §9.1)
and the kernel-path work-skipping ratio (tile-skip effectiveness).

Emits a JSON family row like the other engine benchmarks: interleaved
best/median timings from ``_timing.time_interleaved_stats`` for the
64-source batched BOVM against 64 sequential SOVM runs, plus the
deterministic ``tile_skip_fraction`` (the fraction of (source-tile,
output-tile, frontier-tile) GEMM tiles a frontier/occupancy-aware kernel
may skip, summed over the sweeps of the seeded RMAT fixpoint) — a
hard regression-gate field: it depends only on the graph and the sweep
schedule, not the machine.

    PYTHONPATH=src python -m benchmarks.bench_batching [--out f.json]
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List, Optional

import numpy as np
import jax.numpy as jnp

from repro.core import bovm_msbfs, prepare_graph, sovm_sssp
from repro.graph import generators as gen

from ._timing import time_interleaved_stats


def _tile_skip_fraction(g, adj, srcs) -> float:
    """Deterministic per-sweep tile occupancy accounting."""
    from repro.core import one_hot_frontier, UNREACHED
    f = one_hot_frontier(srcs, adj.shape[0], dtype=jnp.int8)
    dist = jnp.where(f > 0, 0, jnp.full(f.shape, UNREACHED))
    total, skipped = 0, 0
    step = 0
    while step < adj.shape[0]:
        step += 1
        gi, gk, gj = 64 // 64, adj.shape[0] // 128, adj.shape[0] // 128
        f_occ = np.asarray(jnp.any(
            f.reshape(gi, 64, gk, 128) != 0, axis=(1, 3)))
        o_occ = np.asarray(jnp.any(
            dist.reshape(gi, 64, gj, 128) < 0, axis=(1, 3)))
        live = f_occ[:, None, :] & o_occ[:, :, None]     # (gi, gj, gk)
        total += live.size
        skipped += live.size - int(live.sum())
        counts = f.astype(jnp.float32) @ adj.astype(jnp.float32)
        new = (counts > 0) & (dist == UNREACHED)
        dist = jnp.where(new, step, dist)
        f = new.astype(jnp.int8)
        if not bool(jnp.any(new)):
            break
    return skipped / max(total, 1)


def run(quick: bool = False, repeats: int = 3,
        csv: Optional[List[str]] = None) -> Dict:
    g = gen.rmat(10, 8, directed=False, seed=5)
    adj = g.to_dense()
    srcs = jnp.arange(64, dtype=jnp.int32)
    rows = prepare_graph(g).rows

    def seq():
        for s in range(64):
            sovm_sssp(g, s, rows=rows).dist.block_until_ready()

    stats = time_interleaved_stats(
        {"batched": lambda: bovm_msbfs(adj, srcs).dist.block_until_ready(),
         "seq": seq},
        max(2, repeats))
    row: Dict = {"n_nodes": g.n_nodes, "n_edges": g.n_edges,
                 "n_sources": 64}
    for mode, st in stats.items():
        row[f"t_{mode}"] = st["best"]
        row[f"t_{mode}_median"] = st["median"]
    row["batch_speedup"] = row["t_seq"] / row["t_batched"]
    row["tile_skip_fraction"] = round(
        _tile_skip_fraction(g, adj, srcs), 6)

    if csv is not None:
        csv.append(f"batching_bovm64,{row['t_batched'] * 1e6:.0f},"
                   f"speedup_vs_64xSOVM={row['batch_speedup']:.2f}")
        csv.append(f"tile_skip_fraction,,"
                   f"skipped={row['tile_skip_fraction']:.3f}")
    return {
        "benchmark": "bench_batching",
        "families": {"rmat_64src": row},
        # legacy keys some notebooks read
        "batch_speedup": row["batch_speedup"],
        "tile_skip": row["tile_skip_fraction"],
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()
    result = run(quick=args.quick, repeats=args.repeats)
    text = json.dumps(result, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)


if __name__ == "__main__":
    main()
