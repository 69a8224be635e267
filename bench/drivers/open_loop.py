"""Open loop of point-to-point distance queries against
``repro.prepare(g, ...).serve(...)``.

The graph is the configuration's (its ``graph_seed``); the queries are
drawn from ``--seed``.  Arrivals are Poisson at the traffic file's
``rate`` (queries per second) over the window, conditioned on their
count: every seed offers ``rate`` x seconds queries, at times of its
own.  Each query's source is drawn Zipf(``zipf_s``) over an order of
the degree >= 1 vertices drawn from the seed, its target uniformly
among them.  One thread runs the loop: submit every query that is due
(the row cache and the landmark oracle answer at submit), then
``tick()`` (one sweep flush when a query waits), then
``drain_completed()``.  Each query is timed from its due time to its
answer.  After the window closes no query is submitted, and the loop
runs on until every query due in it has its answer, for at most
``drain_s``.

Correct means: every query due in the window is answered, and every
answer to a query from a checked source equals the plain BFS.  The
checked sources are the ``check_hot`` most frequent ones (mostly answered
by the row cache) and ``check_cold`` more drawn from the seed among the
rest (mostly by the oracle and sweep flushes).
"""
from __future__ import annotations

import dataclasses
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro
from bench import reference
from bench.harness import (STREAM_ARRIVALS, STREAM_CHECK, STREAM_KEYS,
                           STREAM_WARM, Context, Outcome)
from repro.serve.engine import GraphQuery


@dataclasses.dataclass
class Served:
    """One window's queries and what became of them."""
    start: float               # host seconds at the window's start
    due: np.ndarray            # (q,) host seconds
    source: np.ndarray
    target: np.ndarray
    lag: np.ndarray            # submit time minus due time
    done: np.ndarray           # answer time, nan where none came
    end: float                 # when the loop gave up waiting
    hops: np.ndarray           # answered hops, -2 where none came
    tier: np.ndarray           # served_by, "" where none came
    flush_s: list              # wall time of each tick that flushed
    backlog_max: int           # most queries waiting after a submit


def schedule(rng: np.random.Generator, order: np.ndarray, rate: float,
             seconds: float, zipf_s: float):
    """(arrival offsets, sources, targets) of one window.  Given their
    count, the arrival times of a Poisson process are independent and
    uniform over the window; the count is fixed, so the seed changes
    when queries come and not how many."""
    at = np.sort(rng.uniform(0.0, seconds, size=round(rate * seconds)))
    w = 1.0 / np.arange(1, len(order) + 1) ** zipf_s
    src = order[rng.choice(len(order), size=len(at), p=w / w.sum())]
    dst = order[rng.integers(len(order), size=len(at))]
    return at, src.astype(np.int64), dst.astype(np.int64)


def setup(ctx: Context):
    """Graph, service, warm-up -> (service, graph, live-vertex order)."""
    g = ctx.graph()
    h = repro.prepare(g, **ctx.facade_options())
    svc = h.serve(clock=time.perf_counter, **ctx.config["serve"])
    deg = np.diff(np.asarray(g.indptr))
    order = ctx.rng(STREAM_KEYS).permutation(np.flatnonzero(deg > 0))
    # landmark tables (the batch program compiles here), then the
    # row slice of every flush size the window can see
    svc.oracle
    dummy = jnp.zeros((svc.config.source_batch, svc.prepared.n_pad),
                      jnp.int32)
    jax.block_until_ready([dummy[:k, :g.n_nodes]
                           for k in range(1, svc.max_batch + 1)])
    # warm traffic fills the row cache as a running service has it
    warm = ctx.traffic["warm_seconds"]
    if warm > 0:
        window(svc, ctx.rng(STREAM_WARM), order, ctx.traffic, warm,
               ctx.spans)
    return svc, g, order


def window(svc, rng, order, traffic: dict, seconds: float,
           spans) -> Served:
    at, src, dst = schedule(rng, order, traffic["rate"], seconds,
                            traffic["zipf_s"])
    q = len(at)
    lag = np.full(q, np.nan)
    done = np.full(q, np.nan)
    hops = np.full(q, -2, np.int64)
    tier = np.full(q, "", dtype=object)
    flush_s = []
    backlog_max = 0
    t0 = time.perf_counter()
    due = t0 + at
    nxt = 0
    while True:
        now = time.perf_counter()
        while nxt < q and due[nxt] <= now:
            query = GraphQuery(qid=nxt, source=int(src[nxt]),
                               target=int(dst[nxt]))
            with spans.span("submit"):
                svc.submit(query)
            lag[nxt] = query.t_submit - due[nxt]
            nxt += 1
        backlog_max = max(backlog_max, svc.pending())
        with spans.span("tick"):
            t_tick = time.perf_counter()
            flushed = svc.tick()
            if flushed:
                flush_s.append(time.perf_counter() - t_tick)
        for query in svc.drain_completed():
            done[query.qid] = query.t_done
            tier[query.qid] = query.served_by
            hops[query.qid] = -2 if query.hops is None else query.hops
        now = time.perf_counter()
        if nxt >= q and not svc.pending():
            break
        if nxt >= q and now > t0 + seconds + traffic["drain_s"]:
            break
        if not svc.pending() and nxt < q and due[nxt] > now:
            with spans.span("wait"):
                time.sleep(due[nxt] - now)
    return Served(start=t0, due=due, source=src, target=dst, lag=lag,
                  done=done, end=time.perf_counter(), hops=hops, tier=tier,
                  flush_s=flush_s, backlog_max=backlog_max)


def run(ctx: Context) -> Outcome:
    tr = ctx.traffic
    svc, g, order = setup(ctx)
    ctx.setup_done()
    with ctx.window():
        s = window(svc, ctx.rng(STREAM_ARRIVALS), order, tr,
                   ctx.window_seconds, ctx.spans)
    memory = ctx.memory_peak()
    indptr, indices, n = np.asarray(g.indptr), np.asarray(g.indices), \
        g.n_nodes
    del svc, g

    latency = s.done - s.due
    answered = ~np.isnan(s.done)
    in_limit = answered & (latency <= tr["limit_s"])
    # the reference: every answer to a query from a checked source
    rng = ctx.rng(STREAM_CHECK)
    srcs, counts = np.unique(s.source[answered], return_counts=True)
    by_count = srcs[np.argsort(-counts, kind="stable")]
    hot, rest = by_count[:tr["check_hot"]], by_count[tr["check_hot"]:]
    cold = rng.choice(rest, size=min(len(rest), tr["check_cold"]),
                      replace=False)
    checked = np.concatenate([hot, cold])
    picked = np.flatnonzero(answered & np.isin(s.source, checked))
    adj = reference.adjacency(indptr, indices, n)
    rows = reference.bfs_rows(adj, checked)
    col = {int(v): i for i, v in enumerate(checked)}
    want = rows[[col[int(v)] for v in s.source[picked]], s.target[picked]]
    wrong = s.hops[picked] != want
    missing = int((~answered).sum())
    # a query that never came counts as waiting until the loop gave up
    lat_ms = np.where(answered, latency, s.end - s.due) * 1e3
    print(f"loop: {len(s.flush_s)} flushes, slowest "
          f"{max(s.flush_s, default=0.0):.3f} s, backlog at most "
          f"{s.backlog_max}; checked {len(picked)} answers from "
          f"{len(checked)} sources", file=sys.stderr)
    return Outcome(
        attempted=len(s.due),
        failed=int((~in_limit).sum()),
        metrics={"query_p95_ms": float(np.percentile(lat_ms, 95)),
                 "goodput_qps": float(in_limit.sum()) / ctx.window_seconds},
        checks={"unanswered": (missing, 0),
                "wrong_answers": (int(wrong.sum()), 0)},
        counters={"tiers": {t: int((s.tier == t).sum())
                            for t in set(s.tier[answered])},
                  "flush_s": s.flush_s,
                  "lag_s": s.lag[~np.isnan(s.lag)].tolist(),
                  "checked": {t: int((s.tier[picked] == t).sum())
                              for t in set(s.tier[picked])}},
        memory_peak_bytes=memory)
