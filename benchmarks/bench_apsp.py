"""Direction-optimized batched APSP: fixed-push vs fixed-pull vs auto.

Runs one MXU-aligned source tile through the core/engine.py driver on each
generator family three times — with the sweep direction pinned to push,
pinned to pull, and chosen per sweep by the engine's occupancy cost
model, as on every backend — and emits a JSON document with per-family
timings plus two advisory booleans:

  * ``auto_no_slower_than_best_everywhere`` — auto within TOLERANCE of
    min(push, pull) on every family;
  * ``auto_beats_worse_on`` — families where auto beats the *worse* fixed
    direction by a real margin (>= 1.25x).

Times are best-of-``repeats`` wall clock of the jitted driver (compile
excluded by a warmup run).  On CPU the engine uses the XLA reference
sweeps, whose cost does not depend on occupancy, so auto's time here is
what the model's choices cost on the CPU, not on the chip; the relative
ordering of the three forms is what is reported, not absolute
throughput.

Two kernel-path comparisons ride along per family (interpret-mode
Pallas): ``t_kernel_fused*`` vs ``t_kernel_push*`` time the fused
multi-sweep blocks (``fused_steps=-1``, whole fixpoint per launch)
against the per-sweep kernel loop, with dist bit-identity and the
``sweeps_fused`` hard-gate field asserted first; ``t_push_packed*`` vs
``t_push_f32*`` time one first-hop sweep through the bit-packed uint32
push kernel against the f32 GEMM push it replaces (Eq. 13 operand
shrink), again bit-identity first.

    PYTHONPATH=src python -m benchmarks.bench_apsp [--quick] [--out f.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import EngineConfig, pack_bits, prepare_graph
from repro.core.autotune import build_plan
from repro.core.engine import apsp_engine
from repro.graph import generators as gen
from repro.kernels.bovm import fused_sweep, packed_push_sweep

from ._timing import (BEAT_MARGIN, TOLERANCE, auto_vs_fixed,
                      time_interleaved_stats)

FAMILIES: Dict[str, Callable] = {
    "grid_road": lambda: gen.grid2d(32, 32),
    "rmat_social": lambda: gen.rmat(10, 8, directed=False, seed=1),
    "ws_citation": lambda: gen.watts_strogatz(1024, 8, 0.05, seed=3),
    "er_uniform": lambda: gen.erdos_renyi(1024, 6.0, directed=False, seed=5),
    "ba_web": lambda: gen.barabasi_albert(1024, 4, seed=6),
    "mycielskian": lambda: gen.mycielskian(9),
}

QUICK_FAMILIES = ("grid_road", "ws_citation", "mycielskian")


def run(quick: bool = False, n_sources: int = 64, repeats: int = 10,
        csv: Optional[List[str]] = None) -> Dict:
    names = QUICK_FAMILIES if quick else tuple(FAMILIES)
    families = {}
    beats_worse = []
    auto_ok_everywhere = True
    for name in names:
        g = FAMILIES[name]()
        pg = prepare_graph(g)
        sources = np.arange(min(n_sources, g.n_nodes), dtype=np.int32)
        row: Dict = {"n_nodes": g.n_nodes, "n_edges": g.n_edges,
                     "n_sources": int(len(sources))}

        last_auto: List = []

        def make_go(mode):
            cfg = EngineConfig(mode=mode, source_batch=64)

            def go():
                res = apsp_engine(pg, sources, config=cfg)
                res.dist.block_until_ready()
                if mode == "auto":
                    last_auto[:] = [res]
            return go

        stats = time_interleaved_stats(
            {m: make_go(m) for m in ("push", "pull", "auto")}, repeats)
        for mode, st in stats.items():
            row[f"t_{mode}"] = st["best"]
            row[f"t_{mode}_median"] = st["median"]
        res = last_auto[0]
        row["sweeps"] = int(res.sweeps)
        row["auto_direction_counts"] = dict(
            zip(("push", "pull", "sparse"),
                np.asarray(res.direction_counts).tolist()))
        auto_vs_fixed(row, ("push", "pull"))
        auto_ok_everywhere &= row["auto_no_slower_than_best"]
        if row["auto_beats_worse"]:
            beats_worse.append(name)

        # --- fused multi-sweep blocks vs the per-sweep kernel loop.
        # Both run the interpret-mode Pallas push kernel; bit-identity of
        # dist and the sweep count is asserted before anything is timed,
        # and ``sweeps_fused`` rides the hard regression gate.  Interpret
        # mode re-traces the whole K-sweep block as XLA ops, so the fused
        # column measures launch structure, not MXU residency.
        cfg_kernel = EngineConfig(mode="push", source_batch=64,
                                  use_kernel=True)
        cfg_fused = EngineConfig(mode="push", source_batch=64,
                                 use_kernel=True, fused_steps=-1)
        res_k = apsp_engine(pg, sources, config=cfg_kernel)
        res_f = apsp_engine(pg, sources, config=cfg_fused)
        np.testing.assert_array_equal(np.asarray(res_f.dist),
                                      np.asarray(res_k.dist))
        assert int(res_f.sweeps) == int(res_k.sweeps)
        row["sweeps_fused"] = int(res_f.sweeps)
        row["fused_equals_per_sweep"] = True

        def make_kernel_go(cfg):
            def go():
                apsp_engine(pg, sources, config=cfg).dist.block_until_ready()
            return go

        for mode, st in time_interleaved_stats(
                {"kernel_push": make_kernel_go(cfg_kernel),
                 "kernel_fused": make_kernel_go(cfg_fused)},
                max(2, repeats // 3)).items():
            row[f"t_{mode}"] = st["best"]
            row[f"t_{mode}_median"] = st["median"]

        # --- packed uint32 push vs the f32 GEMM it replaces: one sweep
        # from the first-hop frontier, bit-identity asserted first.
        s_b = int(len(sources))
        f0 = np.zeros((s_b, pg.n_pad), np.int8)
        f0[np.arange(s_b), np.asarray(sources)] = 1
        d0 = np.full((s_b, pg.n_pad), -1, np.int32)
        d0[np.arange(s_b), np.asarray(sources)] = 0
        f0, d0 = jnp.asarray(f0), jnp.asarray(d0)
        fp, ap = pack_bits(f0 > 0), pg.adj_pull
        bs = 64 if s_b % 64 == 0 else s_b
        new_p, dist_p = packed_push_sweep(fp, ap, d0, 0, bs=bs, bn=128,
                                          wk=4, interpret=True)
        new_g, dist_g = fused_sweep(f0, pg.adj, d0, 0, bs=bs, bn=128,
                                    bk=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(dist_p), np.asarray(dist_g))
        row["packed_push_matches_f32"] = True

        pp = jax.jit(lambda: packed_push_sweep(fp, ap, d0, 0, bs=bs,
                                               bn=128, wk=4,
                                               interpret=True)[1])
        pf = jax.jit(lambda: fused_sweep(f0, pg.adj, d0, 0, bs=bs, bn=128,
                                         bk=128, interpret=True)[1])
        for mode, st in time_interleaved_stats(
                {"push_packed": lambda: pp().block_until_ready(),
                 "push_f32": lambda: pf().block_until_ready()},
                repeats).items():
            row[f"t_{mode}"] = st["best"]
            row[f"t_{mode}_median"] = st["median"]

        # --- autotuned vs default config.  The roofline plan may change
        # tiles, the fused gate and the auto-direction pin, but never
        # results: dist bit-identity is asserted before anything is
        # timed.  ``tuning_plan_checksum`` rides the hard regression
        # gate — the static plan is a pure function of graph shape and
        # backend, so a checksum change means the tuner (or the VMEM
        # budget math behind it) decided differently, not that the
        # machine was slow.  ``autotuned_beats_default`` is advisory
        # (timing-derived).
        plan = build_plan(pg, use_hlo=False)
        row["tuning_plan_checksum"] = plan.checksum()
        cfg_default = EngineConfig(mode="auto", source_batch=64)
        cfg_tuned = dataclasses.replace(cfg_default, tuning=plan)
        res_t = apsp_engine(pg, sources, config=cfg_tuned)
        np.testing.assert_array_equal(np.asarray(res_t.dist),
                                      np.asarray(res.dist))
        row["autotuned_matches_default"] = True
        for mode, st in time_interleaved_stats(
                {"auto_default": make_go("auto"),
                 "auto_tuned": make_kernel_go(cfg_tuned)},
                max(2, repeats // 3)).items():
            row[f"t_{mode}"] = st["best"]
            row[f"t_{mode}_median"] = st["median"]
        row["autotuned_beats_default"] = (
            row["t_auto_tuned"] <= row["t_auto_default"] * TOLERANCE)

        families[name] = row
        if csv is not None:
            csv.append(f"apsp_{name},{row['t_auto'] * 1e6:.1f},"
                       f"auto_vs_best={row['auto_vs_best']:.2f}")
            csv.append(
                f"apsp_{name}_fused,{row['t_kernel_fused'] * 1e6:.1f},"
                f"fused_vs_per_sweep="
                f"{row['t_kernel_fused'] / row['t_kernel_push']:.2f}")
            csv.append(
                f"apsp_{name}_push_packed,"
                f"{row['t_push_packed'] * 1e6:.1f},"
                f"packed_vs_f32="
                f"{row['t_push_packed'] / row['t_push_f32']:.2f}")
    return {
        "benchmark": "bench_apsp",
        "tolerance": TOLERANCE,
        "beat_margin": BEAT_MARGIN,
        "families": families,
        "auto_no_slower_than_best_everywhere": auto_ok_everywhere,
        "auto_beats_worse_on": beats_worse,
        "auto_beats_worse_on_at_least_two": len(beats_worse) >= 2,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--sources", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=10)
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
