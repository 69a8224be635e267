"""Closed loop of batched searches: ``repro.prepare(g, ...).apsp(keys)``
back to back, ``batch`` keys per call.

The graph is the configuration's (its ``graph_seed``), and so are the
job's batches: its vertices of degree >= 1 in an order drawn from the
``graph_seed``, cut ``batch`` at a time (the last batch filled from the
start).  ``--seed`` orders the batches, so every seed runs the same
batches, each once a pass, in an order of its own.
The window runs whole calls until ``--seconds`` has passed; TEPS is the
Graph500 count (each key's component edges, counted once) over the time
from the first call's start to the last call's end.

Correct means: every row reaches exactly the vertices of its key's
component, and ``check_rows`` rows drawn from ``--seed`` equal the plain
BFS, entry for entry.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import repro
from bench import reference
from bench.harness import STREAM_CHECK, STREAM_KEYS, Context, Outcome


@jax.jit
def _reached(dist):
    return jnp.sum(dist >= 0, axis=1, dtype=jnp.int32)


@jax.jit
def _row(dist, i):
    return jax.lax.dynamic_index_in_dim(dist, i, keepdims=False)


def batches(keys: np.ndarray, batch: int) -> np.ndarray:
    """(n, ``batch``) int32: ``keys`` cut into batches in their order,
    the last filled from the start."""
    n = -(-len(keys) // batch)
    return keys[np.arange(n * batch) % len(keys)].reshape(
        n, batch).astype(np.int32)


def run(ctx: Context) -> Outcome:
    batch = ctx.traffic["batch"]
    g = ctx.graph()
    h = repro.prepare(g, **ctx.facade_options())
    deg = np.diff(np.asarray(g.indptr))
    job = batches(ctx.job_rng(STREAM_KEYS).permutation(
        np.flatnonzero(deg > 0)), batch)
    order = ctx.rng(STREAM_KEYS).permutation(len(job))
    rng_check = ctx.rng(STREAM_CHECK)

    # warm-up: the window's shapes, on keys of degree 0 where there are
    # enough (one sweep), else on live keys
    isolated = np.flatnonzero(deg == 0)
    warm = isolated[:batch] if len(isolated) >= batch else job[0]
    res = jax.block_until_ready(h.apsp(warm.astype(np.int32)))
    jax.block_until_ready((_reached(res.dist), _row(res.dist, jnp.int32(0))))
    del res
    ctx.setup_done()

    keys, reached, rows, row_idx, sweeps, dirs = [], [], [], [], [], []
    with ctx.window():
        t0 = time.perf_counter()
        while True:
            k = job[order[len(keys) % len(order)]]
            with ctx.spans.span("apsp"):
                res = jax.block_until_ready(h.apsp(k))
            t1 = time.perf_counter()
            keys.append(k)
            reached.append(_reached(res.dist))
            i = int(rng_check.integers(batch))
            rows.append(_row(res.dist, jnp.int32(i)))
            row_idx.append(i)
            sweeps.append(res.sweeps)
            dirs.append(res.direction_counts)
            if t1 - t0 >= ctx.window_seconds:
                break
    window_s = t1 - t0
    memory = ctx.memory_peak()
    reached = np.asarray(jnp.stack(reached))
    rows = np.asarray(jnp.stack(rows))
    sweeps = [int(s) for s in sweeps]
    dirs = np.asarray(jnp.stack(dirs)).sum(axis=0)
    indptr, indices, n = np.asarray(g.indptr), np.asarray(g.indices), \
        g.n_nodes
    del h, res, g

    # the reference, with the program's state freed
    adj = reference.adjacency(indptr, indices, n)
    comp_edges, comp_size = reference.components(adj)
    keys = np.stack(keys)
    bad = reached != comp_size[keys]            # (calls, batch) searches
    reach_rows = int(bad.sum())
    picked = np.sort(rng_check.choice(
        len(rows), size=min(len(rows), ctx.traffic["check_rows"]),
        replace=False))
    row_idx = np.asarray(row_idx)[picked]
    wrong = rows[picked] != reference.bfs_rows(adj, keys[picked, row_idx])
    bad[picked, row_idx] |= wrong.any(axis=1)
    return Outcome(
        attempted=int(keys.size),
        failed=int(bad.sum()),
        metrics={"teps": float(comp_edges[keys].sum()) / window_s},
        checks={"reach_mismatch_rows": (reach_rows, 0),
                "dist_mismatch_entries": (int(wrong.sum()), 0)},
        counters={"sweeps": sweeps, "direction_counts": dirs.tolist()},
        memory_peak_bytes=memory)
