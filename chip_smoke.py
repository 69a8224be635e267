#!/usr/bin/env python3
"""Chip smoke test: batched multi-source BFS through the repo's entry
points on a TPU, every result checked against an independent reference.

    python chip_smoke.py                # one chip: graph500, kernels, serve
    python chip_smoke.py --chips 4      # four chips: mesh apsp vs device 0

One process holds the chip and starts no other.  Each phase prints one
JSON line — compile seconds (backend compiles and persistent-cache reads,
from ``jax.monitoring``), run seconds (wall time minus tracing, lowering
and compiling), the sizes, and what it checked.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, printing no such line, when JAX finds no TPU
or any check fails; it catches nothing that would let a phase continue.

Phases on one chip:

  * graph500 — Graph500 Kronecker graph, scale 20, edge factor 16,
    undirected; 64 search keys of degree >= 1 drawn from ``--seed``;
    ``prepare(g, mode="sparse", source_batch=64).apsp(keys)``; rows
    checked against scipy's BFS (``core/bfs.py::bfs_scipy``).
  * kernels — SUITE ``grid_road_md`` and ``rmat_social_md`` with 128
    sources under ``mode="push"``, ``"pull"`` and ``"auto"`` (the packed
    Pallas kernels, compiled); the fused boolean block
    (``fused_steps=-1``) on ``grid_road_sm``; tropical dense (min-plus
    kernel) and sparse (XLA: the Pallas sparse relax is interpret-only);
    counting sparse (XLA scatter-add) and push (the counting kernel)
    with their path counts, and a centrality run.  Every row is checked against scipy BFS/Dijkstra or
    ``tests/oracles.py``.
  * serve — ``h.serve(max_batch=16, n_landmarks=8)`` answers 64
    point-to-point queries through ``submit``/``flush``/
    ``drain_completed``, checked against the engine's rows.

With ``--chips 4`` it runs only the mesh path: ``h.apsp(sources,
mesh=...)`` on a (4,) data mesh and a (2, 2) data x model mesh, on the
kernel-phase graph and the scale-20 graph, plus a checkpointed job
killed on the (4,) mesh and resumed on two devices; each must be
bit-identical to the single-device result on device 0.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import oracles  # noqa: E402
import repro as dawn  # noqa: E402
from repro.core.autotune import backend_profile  # noqa: E402
from repro.core.bfs import bfs_scipy  # noqa: E402
from repro.core.jobs import JobResult  # noqa: E402
from repro.graph import generators  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serve.engine import GraphQuery  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


FORMS = {"boolean": ("push", "pull", "sparse"),
         "tropical": ("dense", "sparse"),
         "counting": ("push", "sparse")}


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


class Preempted(Exception):
    """The kill injected into a checkpointed job."""


def expect(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def expect_equal(got, want, what: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = np.argwhere(got != want)[:4].tolist() \
            if got.shape == want.shape else "shape"
        raise SmokeFailure(f"{what}: {got.shape} vs {want.shape}, "
                           f"first mismatches {bad}")


# --------------------------------------------------------------------------
# instrumentation: compile clock and the kernels the engines dispatched
# --------------------------------------------------------------------------

def _covered(spans, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] covered by the union of (start, end) spans —
    nested jits trace inside their caller, so spans overlap."""
    total, reach = 0.0, lo
    for start, end in sorted(spans):
        start, end = max(start, reach), min(end, hi)
        if end > start:
            total += end - start
            reach = end
    return total


class CompileClock:
    """Host time JAX spent tracing, lowering and compiling (a persistent
    cache read counts as compiling), from ``jax.monitoring`` time spans,
    and persistent-cache hits."""

    def __init__(self):
        self.spans = {"compile": [], "setup": []}
        self.cache_hits = 0
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event == COMPILE_EVENT:
            self.spans["compile"].append((start, end))
        if event == COMPILE_EVENT or event in TRACE_EVENTS:
            self.spans["setup"].append((start, end))

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def total(self, kind: str) -> float:
        return _covered(self.spans[kind], 0.0, float("inf"))

    def timed(self, fn):
        """Run ``fn`` to completion on the device -> (result, times):
        ``compile_s`` backend compiles, ``run_s`` the wall time outside
        any trace, lowering or compile."""
        h0 = self.cache_hits
        t0 = time.time()
        out = jax.block_until_ready(fn())
        t1 = time.time()
        compile_s = _covered(self.spans["compile"], t0, t1)
        setup_s = _covered(self.spans["setup"], t0, t1)
        return out, {"wall_s": t1 - t0, "compile_s": compile_s,
                     "trace_s": setup_s - compile_s,
                     "run_s": t1 - t0 - setup_s,
                     "cache_hits": self.cache_hits - h0}


class KernelLog:
    """Every Pallas kernel the engines look up in the registry, with the
    ``interpret`` flag it was traced with.  Wraps the registered kernel
    sets in place; forms a run dispatches are recorded at trace time."""

    def __init__(self):
        self.traced = collections.Counter()
        for semiring in registry.available():
            ks = registry.get(semiring)
            registry.register(dataclasses.replace(
                ks,
                forms={f: self._wrap(semiring, f, k)
                       for f, k in ks.forms.items()},
                fused_forms={f: self._wrap(semiring, f"{f}_fused", k)
                             for f, k in ks.fused_forms.items()}))

    def _wrap(self, semiring, form, kernel):
        def traced(*args, **kw):
            self.traced[(semiring, form,
                         bool(kw.get("interpret", False)))] += 1
            return kernel(*args, **kw)
        return traced

    def compiled(self, semiring, form) -> bool:
        return self.traced[(semiring, form, False)] > 0

    def traced_any(self, semiring, form) -> bool:
        return any(k[:2] == (semiring, form) for k in self.traced)

    def interpreted(self):
        return sorted(f"{s}/{f}" for (s, f, i) in self.traced if i)


def emit(record: dict) -> None:
    print(json.dumps(record, default=_plain), flush=True)


def _plain(x):
    if isinstance(x, (np.integer, np.floating)):
        return x.item()
    if isinstance(x, np.ndarray):
        return x.tolist()
    return str(x)


def counts(res, semiring: str = "boolean") -> dict:
    """Sweeps run per form, keyed by the form's name."""
    return dict(zip(FORMS[semiring],
                    np.asarray(res.direction_counts).tolist()))


def pick_sources(g, k: int, rng) -> np.ndarray:
    """``k`` distinct vertices of out-degree >= 1, sorted."""
    live = np.flatnonzero(np.asarray(g.out_degrees()) >= 1)
    return np.sort(rng.choice(live, size=k, replace=False)).astype(np.int32)


def graph_sizes(g) -> dict:
    return {"n": g.n_nodes, "edges": g.n_edges, "m_pad": g.m_pad,
            "n_pad": g.n_padded(128)}


# --------------------------------------------------------------------------
# one-chip phases
# --------------------------------------------------------------------------

def graph500(seed: int):
    t0 = time.perf_counter()
    g = generators.rmat(20, 16, directed=False, seed=seed)
    keys = pick_sources(g, 64, np.random.default_rng(seed))
    build_s = time.perf_counter() - t0
    return g, keys, build_s


def phase_graph500(clock, seed: int) -> None:
    g, keys, build_s = graph500(seed)
    h = dawn.prepare(g, mode="sparse", source_batch=64)
    res, t = clock.timed(lambda: h.apsp(keys))
    dist = np.asarray(res.dist)
    expect(dist.shape == (64, g.n_nodes), f"dist shape {dist.shape}")
    checked = keys[:8]
    expect_equal(dist[:8], bfs_scipy(g, checked), "graph500 rows vs scipy")
    expect((dist[np.arange(64), keys] == 0).all(), "keys at distance 0")
    sweeps, touched = int(res.sweeps), float(res.edges_touched)
    expect(sweeps > 0 and touched > 0, "graph500 did no work")
    emit({"phase": "graph500", "scale": 20, "edge_factor": 16,
          "keys": 64, **graph_sizes(g), "graph_build_s": build_s, **t,
          "sweeps": sweeps, "edges_touched": touched,
          "direction_counts": counts(res),
          "checked": f"{len(checked)} rows == scipy BFS"})


def _apsp_run(clock, h, sources, ref, **kw):
    res, t = clock.timed(lambda: h.apsp(sources, **kw))
    expect_equal(res.dist, ref, f"apsp {h.options.mode} {kw}")
    return res, t


def phase_kernels(clock, log: KernelLog, seed: int):
    rng = np.random.default_rng(seed + 1)
    for name in ("grid_road_md", "rmat_social_md"):
        g = generators.SUITE[name]()
        sources = pick_sources(g, 128, rng)
        ref = bfs_scipy(g, sources)
        h = dawn.prepare(g, source_batch=128)
        for mode in ("push", "pull", "auto"):
            # same prepared operands, another direction policy
            h.options = dataclasses.replace(h.options, mode=mode)
            res, t = _apsp_run(clock, h, sources, ref)
            dc = counts(res)
            if mode != "auto":
                expect(dc[mode] == int(res.sweeps) > 0,
                       f"{name} {mode}: direction_counts {dc}")
                expect(log.compiled("boolean", mode),
                       f"{name}: compiled boolean {mode} kernel not "
                       f"dispatched ({dict(log.traced)})")
            emit({"phase": "kernels", "graph": name, "semiring": "boolean",
                  "mode": mode, "sources": len(sources), **graph_sizes(g),
                  **t, "sweeps": int(res.sweeps), "direction_counts": dc,
                  "checked": f"{len(sources)} rows == scipy BFS"})

    # the fused boolean block: whole fixpoint in one kernel launch
    gs = generators.SUITE["grid_road_sm"]()
    ss = pick_sources(gs, 128, rng)
    h = dawn.prepare(gs, source_batch=128, mode="push", fused_steps=-1)
    res, t = _apsp_run(clock, h, ss, bfs_scipy(gs, ss))
    expect(log.compiled("boolean", "push_fused"),
           "fused boolean kernel not dispatched (VMEM gate refused?)")
    expect(counts(res)["push"] == int(res.sweeps) > 0, "fused sweeps")
    emit({"phase": "kernels", "graph": "grid_road_sm", "semiring": "boolean",
          "mode": "push", "fused_steps": -1, "sources": len(ss),
          **graph_sizes(gs), **t, "sweeps": int(res.sweeps),
          "direction_counts": counts(res),
          "checked": f"{len(ss)} rows == scipy BFS"})

    # tropical (on rmat_social_md, the last graph above): integer weights
    # keep f32 sums exact, so rows must equal scipy's float64 Dijkstra
    w = rng.integers(1, 9, size=g.m_pad).astype(np.float32)
    ref = oracles.dijkstra_dists(g, w, sources).astype(np.float32)
    for mode in ("dense", "sparse"):
        h = dawn.prepare(g, weights=w, source_batch=128, mode=mode)
        res, t = _apsp_run(clock, h, sources, ref, semiring="tropical")
        dc = counts(res, "tropical")
        expect(dc[mode] == int(res.sweeps) > 0, f"tropical {mode}: {dc}")
        ran = ("Pallas fused_minplus_sweep (compiled)" if mode == "dense"
               else "XLA scatter-min (the Pallas sparse_relax_sweep is "
                    "interpret-only)")
        expect(log.compiled("tropical", "dense") if mode == "dense"
               else not log.traced_any("tropical", "sparse"),
               f"tropical {mode}: {dict(log.traced)}")
        emit({"phase": "kernels", "graph": "rmat_social_md",
              "semiring": "tropical", "mode": mode, "sources": len(sources),
              **graph_sizes(g), **t, "sweeps": int(res.sweeps),
              "direction_counts": dc, "form_ran": ran,
              "checked": f"{len(sources)} rows == scipy Dijkstra"})

    # counting: (dist, sigma) rows by the counting kernel (push) and the
    # XLA scatter-add (sparse), then centrality on the kernel
    sigma_ref = oracles.bfs_sigmas(g, sources)
    expect(sigma_ref.max() < 2 ** 24, "path counts past f32's exact range")
    for mode in ("sparse", "push"):
        h = dawn.prepare(g, source_batch=128, mode=mode)
        res, t = clock.timed(lambda: h.apsp(sources, semiring="counting"))
        expect_equal(res.dist, bfs_scipy(g, sources), f"counting {mode} dist")
        expect_equal(res.sigma, sigma_ref.astype(np.float32),
                     f"counting {mode} sigma")
        dc = counts(res, "counting")
        expect(dc[mode] == int(res.sweeps) > 0, f"counting {mode}: {dc}")
        if mode == "push":
            expect(log.compiled("counting", "push"), "counting kernel")
        emit({"phase": "kernels", "graph": "rmat_social_md",
              "semiring": "counting", "mode": mode, "sources": len(sources),
              **graph_sizes(g), **t, "sweeps": int(res.sweeps),
              "direction_counts": dc,
              "checked": f"{len(sources)} dist rows == scipy BFS, sigma "
                         "rows == oracles.bfs_sigmas"})
    cen, t = clock.timed(lambda: h.centrality(
        sources, measures=("eccentricity", "betweenness")))
    expect_equal(cen.eccentricity, oracles.eccentricities(g, sources),
                 "eccentricity")
    bc_ref = oracles.brandes_betweenness(g, sources)
    expect(np.allclose(cen.betweenness, bc_ref, rtol=1e-4, atol=1e-6),
           "betweenness vs oracles.brandes_betweenness")
    emit({"phase": "kernels", "graph": "rmat_social_md",
          "semiring": "counting", "call": "centrality",
          "sources": len(sources), **t, "sweeps": int(cen.sweeps),
          "checked": "eccentricity == oracles, betweenness ~ oracles "
                     "(rtol 1e-4)"})
    return g


def phase_serve(clock, g, seed: int) -> None:
    rng = np.random.default_rng(seed + 2)
    h = dawn.prepare(g, source_batch=128)
    src = rng.choice(g.n_nodes, size=64).astype(np.int32)
    dst = rng.choice(g.n_nodes, size=64).astype(np.int32)

    def serve():
        svc = h.serve(max_batch=16, n_landmarks=8)
        for i, (s, d) in enumerate(zip(src, dst)):
            svc.submit(GraphQuery(qid=i, source=int(s), target=int(d)))
        done = []
        while svc.pending():
            svc.flush()
            done.extend(svc.drain_completed())
        done.extend(svc.drain_completed())
        return done

    done, t = clock.timed(serve)
    expect(sorted(q.qid for q in done) == list(range(64)),
           f"{len(done)} of 64 queries completed")
    uniq = np.unique(src)
    rows = np.asarray(h.apsp(uniq).dist)
    row_of = {int(s): i for i, s in enumerate(uniq)}
    for q in done:
        expect(q.hops == rows[row_of[q.source], q.target],
               f"query {q.qid} ({q.source}->{q.target}): {q.hops}")
    emit({"phase": "serve", "graph": "rmat_social_md", "queries": 64,
          "max_batch": 16, "n_landmarks": 8, **t,
          "served_by": dict(collections.Counter(q.served_by for q in done)),
          "checked": "64 hops == engine rows"})


# --------------------------------------------------------------------------
# four-chip phase
# --------------------------------------------------------------------------

def _mesh_runs(clock, name, h, sources, single, meshes) -> None:
    for label, mesh in meshes:
        res, t = clock.timed(lambda: h.apsp(sources, mesh=mesh))
        expect_equal(res.dist, single.dist, f"{name} {label} vs device 0")
        expect(int(res.sweeps) == int(single.sweeps),
               f"{name} {label}: {int(res.sweeps)} sweeps vs "
               f"{int(single.sweeps)}")
        emit({"phase": "mesh", "graph": name, "mesh": label,
              "sources": len(sources), **t, "sweeps": int(res.sweeps),
              "direction_counts": np.asarray(res.direction_counts),
              "checked": "dist and sweeps bit-identical to device 0"})


def phase_mesh(clock, seed: int) -> None:
    devices = jax.devices()
    meshes = [("data4", make_mesh((4,), ("data",))),
              ("data2xmodel2", make_mesh((2, 2), ("data", "model")))]
    rng = np.random.default_rng(seed + 1)

    g = generators.SUITE["rmat_social_md"]()
    sources = pick_sources(g, 128, rng)
    h = dawn.prepare(g, source_batch=128)
    single, t = clock.timed(lambda: h.apsp(sources))
    expect_equal(single.dist, bfs_scipy(g, sources), "device 0 vs scipy")
    emit({"phase": "mesh", "graph": "rmat_social_md", "mesh": "device0",
          "sources": len(sources), **graph_sizes(g), **t,
          "sweeps": int(single.sweeps), "checked": "rows == scipy BFS"})
    _mesh_runs(clock, "rmat_social_md", h, sources, single, meshes)

    # elastic resume: killed after its first chunk on four devices,
    # resumed from the checkpoint on two
    two = jax.sharding.Mesh(np.array(devices[:2]), ("data",))
    with tempfile.TemporaryDirectory() as ckpt:
        def kill(chunk):
            raise Preempted(f"killed after chunk {chunk}")
        try:
            h.apsp(sources, mesh=meshes[0][1], checkpoint_dir=ckpt,
                   chunk_size=64, on_chunk=kill)
        except Preempted:
            pass
        else:
            raise SmokeFailure("the injected kill did not fire")
        job, t = clock.timed(lambda: h.apsp(
            sources, mesh=two, checkpoint_dir=ckpt, chunk_size=64))
    expect(isinstance(job, JobResult) and job.chunks_restored >= 1,
           f"resume restored nothing: {job.chunks_restored}")
    expect_equal(job.dist, single.dist, "resumed job vs device 0")
    emit({"phase": "mesh", "graph": "rmat_social_md",
          "mesh": "resume data4 -> data2", "sources": len(sources), **t,
          "chunks_restored": job.chunks_restored,
          "checked": "resumed dist bit-identical to device 0"})

    g, keys, build_s = graph500(seed)
    h = dawn.prepare(g, mode="sparse", source_batch=64)
    single, t = clock.timed(lambda: h.apsp(keys))
    expect_equal(np.asarray(single.dist)[:8], bfs_scipy(g, keys[:8]),
                 "graph500 device 0 vs scipy")
    emit({"phase": "mesh", "graph": "graph500-s20", "mesh": "device0",
          "keys": 64, **graph_sizes(g), "graph_build_s": build_s, **t,
          "sweeps": int(single.sweeps), "checked": "8 rows == scipy BFS"})
    _mesh_runs(clock, "graph500-s20", h, keys, single, meshes)


# --------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX found {dev.platform!r} devices "
              f"({len(devices)}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache(ROOT)
    emit({"phase": "start", "platform": dev.platform,
          "kind": dev.device_kind, "count": len(devices),
          "jax": jax.__version__, "compile_cache": cache,
          "roofline_profile": backend_profile().name})
    clock = CompileClock()
    log = KernelLog()
    if args.chips == 4:
        phase_mesh(clock, args.seed)
    else:
        phase_graph500(clock, args.seed)
        g = phase_kernels(clock, log, args.seed)
        phase_serve(clock, g, args.seed)
    bad = log.interpreted()
    expect(not bad, f"kernels ran in interpret mode: {bad}")
    emit({"phase": "done", "compile_s": clock.total("compile"),
          "trace_s": clock.total("setup") - clock.total("compile"),
          "cache_hits": clock.cache_hits,
          "kernels_traced": sorted(f"{s}/{f}" for s, f, _ in log.traced)})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
