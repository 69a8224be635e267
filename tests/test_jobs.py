"""Resumable-job layer (core/jobs.py): preemption-safe checkpoint/resume
bit-identical to uninterrupted runs, fault injection (kill between /
within checkpoint intervals, corrupt shards, dead hosts), and the
elastic restore-onto-a-smaller-mesh walk.

Kills are injected through the job's ``on_chunk`` seam (raising
simulates preemption after that chunk's checkpoint was submitted; with
``checkpoint_interval > 1`` the newest chunks are not yet checkpointed,
which simulates dying inside an interval).  Mesh tests run in a
subprocess so jax initializes with 8 virtual devices.
"""
import os
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from oracles import adversarial_families, bfs_dists

import repro as dawn
from repro.core import SweepOptions
from repro.core.engine import EngineConfig, apsp_engine
from repro.core.centrality import CentralityConfig, counting_apsp
from repro.core.jobs import JobMismatchError, JobResult, run_sweep_job
from repro.graph.csr import CSRGraph
from repro.train import checkpoint as C


class _Preempt(RuntimeError):
    """Injected kill."""


def _kill_after(chunk_idx):
    def on_chunk(k):
        if k == chunk_idx:
            raise _Preempt(f"killed after chunk {k}")
    return on_chunk


def _graphs():
    keep = ("star_in", "path", "two_components", "random_ragged")
    return {name: CSRGraph.from_edges(src, dst, n)
            for name, src, dst, n in adversarial_families(seed=0)
            if name in keep}


OPTS = SweepOptions(source_batch=8, mode="auto")


def _assert_results_equal(a: JobResult, b: JobResult):
    np.testing.assert_array_equal(a.dist, b.dist)
    if a.sigma is not None or b.sigma is not None:
        np.testing.assert_array_equal(a.sigma, b.sigma)
    assert a.sweeps == b.sweeps
    np.testing.assert_array_equal(a.direction_counts, b.direction_counts)
    assert a.edges_touched == b.edges_touched
    assert a.chunks_total == b.chunks_total


def test_job_matches_engine_boolean_and_counting():
    """Chunked job aggregation == one engine call (dist, sigma, sweeps,
    direction_counts, edges_touched) when the chunking matches the
    engine's internal tiling."""
    g = _graphs()["random_ragged"]
    srcs = np.arange(24, dtype=np.int32)
    job = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                        chunk_size=8)
    eng = apsp_engine(g, srcs, config=OPTS.to(EngineConfig, lenient=True))
    np.testing.assert_array_equal(job.dist, np.asarray(eng.dist))
    np.testing.assert_array_equal(job.dist, bfs_dists(g, srcs))
    assert job.sweeps == int(eng.sweeps)
    np.testing.assert_array_equal(job.direction_counts,
                                  np.asarray(eng.direction_counts))
    assert job.edges_touched == float(eng.edges_touched)
    assert (job.chunks_total, job.chunks_computed,
            job.chunks_restored) == (3, 3, 0)

    jc = run_sweep_job(g, srcs, workload="counting", options=OPTS,
                       chunk_size=8)
    ec = counting_apsp(g, srcs, config=OPTS.to(CentralityConfig,
                                               lenient=True))
    np.testing.assert_array_equal(jc.dist, np.asarray(ec.dist))
    np.testing.assert_array_equal(jc.sigma, np.asarray(ec.sigma))
    assert jc.sweeps == int(ec.sweeps)


@pytest.mark.parametrize("workload", ["boolean", "tropical", "counting"])
def test_resume_bit_identical_across_families(workload):
    """Kill after the first chunk, resume in a fresh invocation: every
    result field is bit-identical to the uninterrupted run, on every
    adversarial family."""
    rng = np.random.default_rng(3)
    for name, g in _graphs().items():
        w = rng.uniform(0.5, 4.0, g.m_pad).astype(np.float32) \
            if workload == "tropical" else None
        srcs = np.arange(min(24, g.n_nodes), dtype=np.int32)
        full = run_sweep_job(g, srcs, workload=workload, weights=w,
                             options=OPTS, chunk_size=8)
        with tempfile.TemporaryDirectory() as d:
            with pytest.raises(_Preempt):
                run_sweep_job(g, srcs, workload=workload, weights=w,
                              options=OPTS, chunk_size=8,
                              checkpoint_dir=d, on_chunk=_kill_after(0))
            res = run_sweep_job(g, srcs, workload=workload, weights=w,
                                options=OPTS, chunk_size=8,
                                checkpoint_dir=d)
        _assert_results_equal(res, full)
        assert res.chunks_restored >= 1, name
        assert res.chunks_computed == res.chunks_total - \
            res.chunks_restored
        assert res.restored_step == res.chunks_restored
        assert res.corrupt_skipped == 0


def test_kill_inside_checkpoint_interval_recomputes_tail():
    """checkpoint_interval=2 and a kill after chunk 2 (0-indexed):
    chunks 0-1 are checkpointed, chunk 2's work is lost and must be
    recomputed — the resumed result is still bit-identical."""
    g = _graphs()["random_ragged"]
    srcs = np.arange(32, dtype=np.int32)          # 4 chunks of 8
    full = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                         chunk_size=8)
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(_Preempt):
            run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                          chunk_size=8, checkpoint_dir=d,
                          checkpoint_interval=2, on_chunk=_kill_after(2))
        assert C.latest_step(d) == 2              # chunk 2 never landed
        res = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                            chunk_size=8, checkpoint_dir=d,
                            checkpoint_interval=2)
    _assert_results_equal(res, full)
    assert res.chunks_restored == 2
    assert res.chunks_computed == 2


def test_corrupt_checkpoint_falls_back_to_older():
    """Flip bytes in the newest checkpoint's shard: resume counts it as
    corrupt, falls back to the next-older intact checkpoint, and still
    reproduces the uninterrupted result."""
    g = _graphs()["random_ragged"]
    srcs = np.arange(32, dtype=np.int32)
    full = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                         chunk_size=8)
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(_Preempt):
            run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                          chunk_size=8, checkpoint_dir=d,
                          on_chunk=_kill_after(2))
        assert C.latest_step(d) == 3
        with open(os.path.join(d, "step_000000003", "0000.bin"),
                  "r+b") as f:
            f.write(b"\xde\xad\xbe\xef")
        res = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                            chunk_size=8, checkpoint_dir=d)
    _assert_results_equal(res, full)
    assert res.corrupt_skipped == 1
    assert res.restored_step == 2
    assert res.chunks_restored == 2


def test_mismatched_job_refuses_to_resume():
    """A checkpoint_dir written by a different job (other sources, other
    graph content) raises JobMismatchError instead of silently resuming
    or overwriting."""
    gs = _graphs()
    g = gs["random_ragged"]
    with tempfile.TemporaryDirectory() as d:
        run_sweep_job(g, np.arange(16), workload="boolean", options=OPTS,
                      chunk_size=8, checkpoint_dir=d)
        with pytest.raises(JobMismatchError):
            run_sweep_job(g, np.arange(24), workload="boolean",
                          options=OPTS, chunk_size=8, checkpoint_dir=d)
        with pytest.raises(JobMismatchError):
            run_sweep_job(gs["path"], np.arange(16), workload="boolean",
                          options=OPTS, chunk_size=8, checkpoint_dir=d)


def test_finished_job_restores_without_compute():
    """Re-running a completed checkpointed job restores everything and
    sweeps nothing."""
    g = _graphs()["path"]
    srcs = np.arange(16, dtype=np.int32)
    with tempfile.TemporaryDirectory() as d:
        first = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                              chunk_size=8, checkpoint_dir=d)
        again = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                              chunk_size=8, checkpoint_dir=d)
    _assert_results_equal(again, first)
    assert again.chunks_computed == 0
    assert again.chunks_restored == again.chunks_total
    assert again.checkpoints_written == 0
    # resume=False recomputes from scratch instead
    with tempfile.TemporaryDirectory() as d:
        run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                      chunk_size=8, checkpoint_dir=d)
        redo = run_sweep_job(g, srcs, workload="boolean", options=OPTS,
                             chunk_size=8, checkpoint_dir=d,
                             resume=False)
    assert redo.chunks_computed == redo.chunks_total
    _assert_results_equal(redo, first)


def test_facade_checkpointed_apsp():
    """dawn.prepare(g).apsp(checkpoint_dir=...) routes through the job
    layer, survives a kill, and carries the resume counters."""
    g = _graphs()["two_components"]
    h = dawn.prepare(g, source_batch=8)
    srcs = np.arange(24, dtype=np.int32)
    plain = h.apsp(srcs)
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(_Preempt):
            h.apsp(srcs, checkpoint_dir=d, chunk_size=8,
                   on_chunk=_kill_after(0))
        res = h.apsp(srcs, checkpoint_dir=d, chunk_size=8)
    assert isinstance(res, JobResult)
    np.testing.assert_array_equal(res.dist, np.asarray(plain.dist))
    assert res.sweeps == int(plain.sweeps)
    assert res.chunks_restored == 1 and res.restored_step == 1


def test_mutated_dynamic_graph_invalidates_checkpoints():
    """The job fingerprint pins the dynamic graph's content epoch: a
    mutation between runs must raise, not resume stale distances."""
    from repro.graph.dynamic import DynamicCSRGraph
    _, src, dst, n = [f for f in adversarial_families(0)
                      if f[0] == "path"][0]
    dg = DynamicCSRGraph.from_edges(src, dst, n)
    srcs = np.arange(8, dtype=np.int32)
    with tempfile.TemporaryDirectory() as d:
        run_sweep_job(dg, srcs, workload="boolean", options=OPTS,
                      chunk_size=4, checkpoint_dir=d)
        dg.insert_edges([0], [n - 1])
        with pytest.raises(JobMismatchError):
            run_sweep_job(dg, srcs, workload="boolean", options=OPTS,
                          chunk_size=4, checkpoint_dir=d)


# -------------------------------------------------------------------------
# sharded + elastic: subprocess with 8 virtual devices
# -------------------------------------------------------------------------

def _run(body: str, devices: int = 8):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count={devices}"
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-4000:]
    return res.stdout


@pytest.mark.slow
def test_sharded_job_elastic_resume_onto_smaller_mesh():
    """Acceptance: a sharded counting (betweenness-grade) job killed
    mid-run, whose host loss is detected by HeartbeatMonitor on an
    injected clock, resumes via plan_remesh + mesh_from_plan +
    restore(shardings=) onto a SMALLER mesh — and is bit-identical
    (dist, sigma, sweeps, counters) to the uninterrupted large-mesh run
    and the single-device engine."""
    out = _run("""
        import sys, tempfile; sys.path.insert(0, "tests")
        import numpy as np, jax
        from oracles import bfs_sigmas
        from repro.graph import generators as gen
        from repro.core import SweepOptions
        from repro.core.centrality import CentralityConfig, counting_apsp
        from repro.core.jobs import run_sweep_job
        from repro.launch.mesh import make_mesh, mesh_from_plan
        from repro.train import fault_tolerance as FT

        g = gen.rmat(8, 6, directed=False, seed=5)       # n = 256
        srcs = np.arange(32, dtype=np.int32)
        # direction_counts must survive the mesh change bit-for-bit, so
        # pin the form (the auto cost model's pmean'd stats are not
        # mesh-shape invariant; dist/sigma/sweeps are under any mode)
        opts = SweepOptions(source_batch=8, mode="dense")
        big = make_mesh((4, 2), ("data", "model"))

        full = run_sweep_job(g, srcs, workload="counting", mesh=big,
                             options=opts, chunk_size=8)
        single = counting_apsp(g, srcs, config=opts.to(
            CentralityConfig, lenient=True))
        np.testing.assert_array_equal(full.dist, np.asarray(single.dist))
        np.testing.assert_array_equal(full.sigma,
                                      np.asarray(single.sigma))
        np.testing.assert_allclose(full.sigma, bfs_sigmas(g, srcs))
        assert full.sweeps == int(single.sweeps)
        assert full.edges_touched > 0

        class Boom(RuntimeError): pass
        def kill(k):
            if k == 1:
                raise Boom()

        d = tempfile.mkdtemp()
        try:
            run_sweep_job(g, srcs, workload="counting", mesh=big,
                          options=opts, chunk_size=8, checkpoint_dir=d,
                          on_chunk=kill)
        except Boom:
            pass

        # virtual 2-host world: host 1 stops beating -> dead -> replan
        t = [0.0]
        mon = FT.HeartbeatMonitor(2, interval_s=10.0, dead_after=3,
                                  clock=lambda: t[0])
        assert mon.sweep() == []          # construction-time last_beat
        for step in range(1, 10):
            t[0] = 10.0 * step
            mon.beat(0)
            if step < 2:
                mon.beat(1)
        dead = mon.sweep()
        assert dead == [1], dead
        alive_chips = len(mon.alive_hosts) * 4
        plan = FT.plan_remesh(alive_chips, model_parallel=2,
                              restore_step=None, dropped_hosts=(1,))
        assert plan.mesh_shape == (2, 2)
        small = mesh_from_plan(plan)

        res = run_sweep_job(g, srcs, workload="counting", mesh=small,
                            options=opts, chunk_size=8, checkpoint_dir=d)
        assert res.chunks_restored == 2 and res.chunks_computed == 2
        np.testing.assert_array_equal(res.dist, full.dist)
        np.testing.assert_array_equal(res.sigma, full.sigma)
        assert res.sweeps == full.sweeps
        np.testing.assert_array_equal(res.direction_counts,
                                      full.direction_counts)
        assert res.edges_touched == full.edges_touched
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_boolean_job_resume_and_edge_counter_parity():
    """Boolean sharded job: kill + resume onto a source-only mesh is
    bit-identical, and the new sharded edges_touched counter is
    mesh-shape invariant (exact integer partial sums)."""
    out = _run("""
        import sys, tempfile; sys.path.insert(0, "tests")
        import numpy as np, jax
        from oracles import bfs_dists
        from repro.graph import generators as gen
        from repro.core import SweepOptions, ShardedConfig
        from repro.core.distributed import sharded_apsp
        from repro.core.jobs import run_sweep_job
        from repro.launch.mesh import make_mesh

        g = gen.erdos_renyi(237, 3.0, seed=9)
        srcs = np.arange(24, dtype=np.int32)
        opts = SweepOptions(source_batch=8, mode="dense")
        big = make_mesh((2, 4), ("data", "model"))
        small = make_mesh((2,), ("data",))

        a = sharded_apsp(g, srcs, mesh=big,
                         config=ShardedConfig(mode="dense"))
        b = sharded_apsp(g, srcs, mesh=small,
                         config=ShardedConfig(mode="dense"))
        assert float(a.edges_touched) == float(b.edges_touched) > 0

        full = run_sweep_job(g, srcs, workload="boolean", mesh=big,
                             options=opts, chunk_size=8)
        np.testing.assert_array_equal(full.dist, bfs_dists(g, srcs))

        class Boom(RuntimeError): pass
        def kill(k):
            if k == 0:
                raise Boom()
        d = tempfile.mkdtemp()
        try:
            run_sweep_job(g, srcs, workload="boolean", mesh=big,
                          options=opts, chunk_size=8, checkpoint_dir=d,
                          on_chunk=kill)
        except Boom:
            pass
        res = run_sweep_job(g, srcs, workload="boolean", mesh=small,
                            options=opts, chunk_size=8, checkpoint_dir=d)
        assert res.chunks_restored == 1
        np.testing.assert_array_equal(res.dist, full.dist)
        assert res.sweeps == full.sweeps
        np.testing.assert_array_equal(res.direction_counts,
                                      full.direction_counts)
        assert res.edges_touched == full.edges_touched
        print("OK")
    """)
    assert "OK" in out
