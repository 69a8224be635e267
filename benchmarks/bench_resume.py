"""Resumable-job layer (core/jobs.py): checkpointed sweep jobs and
preemption-safe resume — overhead and bit-identity, as JSON.

For each family, a counting-semiring APSP job (dist + sigma — the
betweenness front half) runs three ways:

  (a) **full** — all chunks in one go, checkpointing every chunk into a
      fresh directory (the steady-state production configuration);
  (b) **killed** — the same job preempted via the ``on_chunk`` seam
      after half the chunks (the checkpoint directory it leaves behind
      is the resume fixture);
  (c) **resumed** — the same call pointed at a copy of the killed run's
      directory, restoring the newest checkpoint and sweeping only the
      missing tail.

Resume is asserted bit-identical to the full run (dist, sigma, sweeps,
direction counts) before any timing — a resumed job that drifts is a
bug, not a data point.  The JSON rides the hard regression gate with
the determinism fields: ``chunks_total`` / ``sweeps`` /
``dist_checksum`` / ``sigma_checksum`` (exact integer sums),
``checkpoints_written``, and the resumed-sweep accounting
(``resumed_chunks`` / ``recomputed_chunks`` / ``resume_equals_full``).
Timings (``t_full`` vs ``t_resume``, checkpoint I/O included) are
advisory medians: resuming half a job should cost roughly half a run
plus one restore.

Single-device by construction — mesh-routed jobs are exercised by the
subprocess tests (tests/test_jobs.py); their ``direction_counts`` are
mesh-shape dependent, which would make the baseline machine-specific.

    PYTHONPATH=src python -m benchmarks.bench_resume [--quick] [--out f.json]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
from typing import Callable, Dict, List, Optional

import numpy as np

from ._timing import time_interleaved_stats


def _families() -> Dict[str, Callable]:
    # lazy: main() may need to set XLA_FLAGS before anything imports jax
    from repro.graph import generators as gen
    return {
        "grid_road": lambda: gen.grid2d(32, 32),
        "ws_citation": lambda: gen.watts_strogatz(1024, 8, 0.05, seed=3),
    }


QUICK_FAMILIES = ("grid_road",)


class _Preempt(RuntimeError):
    pass


def _kill_after(chunk_idx: int):
    def on_chunk(k: int) -> None:
        if k == chunk_idx:
            raise _Preempt(f"injected preemption after chunk {k}")
    return on_chunk


def run(quick: bool = False, n_sources: int = 32, repeats: int = 3,
        csv: Optional[List[str]] = None) -> Dict:
    from repro.core.jobs import run_sweep_job
    from repro.core.options import SweepOptions

    chunk_size = 8
    # pinned form: the recorded baseline was taken in it; dist, sigma
    # and sweeps are form-invariant
    opts = SweepOptions(source_batch=chunk_size, mode="sparse")
    names = QUICK_FAMILIES if quick else tuple(_families())
    families = {}
    for name in names:
        g = _families()[name]()
        sources = np.arange(min(n_sources, g.n_nodes), dtype=np.int32)

        def job(ckpt_dir, on_chunk=None):
            return run_sweep_job(
                g, sources, workload="counting", options=opts,
                chunk_size=chunk_size, checkpoint_dir=ckpt_dir,
                checkpoint_interval=1, on_chunk=on_chunk)

        with tempfile.TemporaryDirectory() as td:
            full = job(os.path.join(td, "full"))
            kill_at = full.chunks_total // 2 - 1   # die after half
            fixture = os.path.join(td, "killed")
            try:
                job(fixture, on_chunk=_kill_after(kill_at))
            except _Preempt:
                pass
            resume_dir = os.path.join(td, "resume0")
            shutil.copytree(fixture, resume_dir)
            resumed = job(resume_dir)

            # bit-identical before any timing
            np.testing.assert_array_equal(resumed.dist, full.dist)
            np.testing.assert_array_equal(resumed.sigma, full.sigma)
            assert resumed.sweeps == full.sweeps
            np.testing.assert_array_equal(resumed.direction_counts,
                                          full.direction_counts)
            assert resumed.chunks_restored == kill_at + 1
            assert resumed.chunks_restored + resumed.chunks_computed \
                == full.chunks_total

            row: Dict = {
                "n_nodes": g.n_nodes, "n_edges": g.n_edges,
                "n_sources": int(len(sources)),
                "chunks_total": full.chunks_total,
                "sweeps": full.sweeps,
                # exact integer sums in int64/f32 — any drift means the
                # resumed job computed different shortest paths
                "dist_checksum": int(
                    np.asarray(full.dist, np.int64).sum()),
                "sigma_checksum": float(np.asarray(full.sigma).sum()),
                "checkpoints_written": full.checkpoints_written,
                "resumed_chunks": resumed.chunks_restored,
                "recomputed_chunks": resumed.chunks_computed,
                "resume_equals_full": True,   # asserted above
            }

            counter = [0]

            def go_full():
                counter[0] += 1
                job(os.path.join(td, f"tf{counter[0]}"))

            def go_resume():
                counter[0] += 1
                d = os.path.join(td, f"tr{counter[0]}")
                shutil.copytree(fixture, d)
                job(d)

            stats = time_interleaved_stats(
                {"full": go_full, "resume": go_resume}, repeats)
            for mode, st in stats.items():
                row[f"t_{mode}"] = st["best"]
                row[f"t_{mode}_median"] = st["median"]
            row["resume_speedup"] = row["t_full"] / row["t_resume"]
        families[name] = row
        if csv is not None:
            csv.append(
                f"resume_{name},{row['t_resume'] * 1e6:.1f},"
                f"resume_speedup={row['resume_speedup']:.2f}x")
    return {
        "benchmark": "bench_resume",
        "chunk_size": chunk_size,
        "families": families,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--sources", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
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
