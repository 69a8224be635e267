"""Paper Tables 7/8 analogue: DAWN vs BFS baselines across the graph suite.

Offline substitutions (SuiteSparse unavailable): matched synthetic graph
families; 'GAP' stand-in = scipy.sparse.csgraph C BFS; 'queueBFS' = paper
Alg. 3 in numpy.  DAWN runs jitted on CPU — speedups are conservative for
the matrix formulation (no MXU here).
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.dawn import GRAPH_SUITE, SOURCE_SET_SIZE
from repro.core import (bfs_queue_numpy, bfs_scipy, pack_bits,
                        prepare_graph, sovm_sssp, sssp)
from repro.core.sovm import sovm_msbfs
from repro.kernels.bovm import fused_sweep, packed_push_sweep


def _time(fn: Callable, repeats: int = 5) -> float:
    fn()  # warmup / jit
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def run(n_sources: int = 16, csv: List[str] | None = None) -> Dict:
    rng = np.random.default_rng(0)
    buckets = {"<1x": 0, "1-2x": 0, "2-4x": 0, "4-16x": 0, ">16x": 0}
    speedups = []
    for name, make in GRAPH_SUITE.items():
        g = make()
        sources = rng.integers(0, g.n_nodes, n_sources).astype(np.int32)
        rows = prepare_graph(g).rows

        def dawn_run():
            for s in sources:
                sovm_sssp(g, int(s), rows=rows).dist.block_until_ready()

        def gap_run():
            for s in sources:
                bfs_scipy(g, int(s))

        t_dawn = _time(dawn_run, repeats=3)
        t_gap = _time(gap_run, repeats=3)
        sp = t_gap / t_dawn
        speedups.append(sp)
        if sp < 1:
            buckets["<1x"] += 1
        elif sp < 2:
            buckets["1-2x"] += 1
        elif sp < 4:
            buckets["2-4x"] += 1
        elif sp < 16:
            buckets["4-16x"] += 1
        else:
            buckets[">16x"] += 1
        if csv is not None:
            csv.append(f"sssp_{name},{t_dawn / n_sources * 1e6:.1f},"
                       f"speedup_vs_gap={sp:.2f}")
    geo = float(np.exp(np.mean(np.log(speedups))))

    # Eq. 13 in practice: the bit-packed uint32 push operand vs the f32
    # GEMM push it replaces — one first-hop sweep, batch of 64 sources,
    # on the first suite graph, bit-identity asserted before timing.
    # Interpret-mode Pallas on CPU, so the ratio tracks lowered-op count
    # (the 32x operand shrink), not MXU throughput.
    g0 = next(iter(GRAPH_SUITE.values()))()
    pg = prepare_graph(g0)
    srcs = rng.integers(0, g0.n_nodes, 64).astype(np.int32)
    f0 = np.zeros((64, pg.n_pad), np.int8)
    f0[np.arange(64), srcs] = 1
    d0 = np.full((64, pg.n_pad), -1, np.int32)
    d0[np.arange(64), srcs] = 0
    f0, d0 = jnp.asarray(f0), jnp.asarray(d0)
    fp = pack_bits(f0 > 0)
    pp = jax.jit(lambda: packed_push_sweep(fp, pg.adj_pull, d0, 0, bs=64,
                                           bn=128, wk=4, interpret=True)[1])
    pf = jax.jit(lambda: fused_sweep(f0, pg.adj, d0, 0, bs=64, bn=128,
                                     bk=128, interpret=True)[1])
    np.testing.assert_array_equal(np.asarray(pp()), np.asarray(pf()))
    t_packed = _time(lambda: pp().block_until_ready(), repeats=3)
    t_f32 = _time(lambda: pf().block_until_ready(), repeats=3)
    if csv is not None:
        csv.append(f"sssp_suite_geomean,,speedup={geo:.3f}")
        csv.append(f"sssp_speedup_buckets,,{buckets}")
        csv.append(f"sssp_push_packed,{t_packed * 1e6:.1f},"
                   f"packed_vs_f32={t_packed / t_f32:.2f}")
    return {"buckets": buckets, "geomean": geo, "speedups": speedups,
            "push_packed_seconds": t_packed, "push_f32_seconds": t_f32}


if __name__ == "__main__":
    rows: List[str] = []
    out = run(csv=rows)
    print("\n".join(rows))
