"""Batched graph-query serving: tiered admission + bucketed micro-batching.

A :class:`GraphService` answers :class:`GraphQuery` requests through a
three-level serving tier —

  1. **row cache** — an LRU of distance rows earlier sweeps already
     computed: repeated queries from a hot source cost one O(n) lookup;
  2. **landmark oracle** (serve/oracle.py) — O(|landmarks|)
     triangle-inequality bounds with an exactness certificate; only
     *certified* answers are served (bit-identical to a sweep by
     construction);
  3. **exact sweep fallback** — uncertified misses are bucketed by
     predicted sweep count and micro-batched into one direction-optimized
     multi-source run (core/engine.py) per flush, with per-query
     deadlines driving a deadline-aware flush policy (``tick``).

so graph analytics share one continuous-batching loop instead of
needing a separate deployment.

The service also fronts **mutable graphs**: built over a
:class:`repro.graph.dynamic.DynamicCSRGraph`, every entry point
(``submit`` / ``flush`` / ``tick``) first compares the graph's content
``epoch`` against the epoch the cached operands were prepared at.  On a
mismatch the prepared operands are rebuilt from the merged view and
every derived cache — the LRU row cache, the betweenness vector, the
sharded operands, and the landmark label tables behind the oracle — is
invalidated (the oracle rebuilds lazily on next touch).  A stale
certified answer is therefore impossible: admission never consults a
cache whose epoch disagrees with the graph.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import OrderedDict, deque
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from .. import obs
from ..core.centrality import (MEASURES, CentralityConfig, betweenness,
                               centrality)
from ..core.distributed import (ShardedConfig, ShardedOperands,
                                prepare_sharded, sharded_apsp)
from ..core.engine import EngineConfig, PreparedGraph, apsp_engine_blocks, \
    prepare_graph
from ..core.weighted import (PreparedWeightedGraph, WeightedConfig,
                             prepare_weighted, weighted_apsp)
from .oracle import DistanceOracle, select_top_k


@dataclasses.dataclass
class GraphQuery:
    """A ``shortest_path`` request served by the batching loop.

    ``target=None`` returns the full distance vector from ``source``;
    otherwise ``hops`` is the shortest unweighted path length (or -1 when
    unreachable).  ``weighted=True`` routes through the tropical-semiring
    engine instead: ``dist`` becomes float32 (inf = unreachable) and a
    target query fills ``cost`` (the weighted distance) rather than
    ``hops``.

    ``analytics`` turns the query into a centrality request: a tuple of
    measure names from :data:`repro.core.centrality.MEASURES`
    ("closeness" / "harmonic" / "eccentricity" / "betweenness").  The
    per-source measures of every analytics query in a flush batch into
    ONE jit-batched multi-source run (core/centrality.py); betweenness —
    a whole-graph analytic — is computed once per service (through the
    sharded executor when a mesh is configured), cached, and answered
    from the cache.  Results land in ``analytics_result`` keyed by
    measure, all for node ``source``.

    ``k_nearest=k`` asks for the k nearest reachable targets instead:
    ``nearest`` is filled with (node, hops) pairs sorted by (distance,
    node id) — ties deterministic, identical whether the answer came
    from the oracle or the exact sweep fallback.

    ``deadline`` is a per-query latency budget in seconds from submit.
    The deadline-aware flush policy (:meth:`GraphService.tick`) tries to
    serve the query before it trips; a query whose deadline has already
    passed when its batch is formed is *surfaced* as ``expired=True``
    (``served_by="expired"``, no result) rather than silently dropped or
    allowed to pad-waste a live batch.

    After completion, ``served_by`` records the serving tier ("cache" /
    "oracle" / "sweep" / "sharded" / "expired") and ``certified`` is
    True when the answer was proven exact *without* running a sweep
    (row-cache or certified-oracle answers — both bit-identical to the
    sweep the fallback would have run).
    """
    qid: int
    source: int
    target: Optional[int] = None
    weighted: bool = False
    analytics: Optional[tuple] = None
    k_nearest: Optional[int] = None
    deadline: Optional[float] = None
    dist: Optional[np.ndarray] = None
    hops: Optional[int] = None
    cost: Optional[float] = None
    analytics_result: Optional[Dict[str, float]] = None
    nearest: Optional[List[Tuple[int, int]]] = None
    certified: bool = False
    served_by: Optional[str] = None
    expired: bool = False
    t_submit: float = 0.0
    t_done: float = 0.0
    t_deadline: float = math.inf
    _seq: int = dataclasses.field(default=0, repr=False)


class GraphService:
    """Tiered serving of shortest-path queries over one prepared graph.

    **Admission (at submit):** queries that can be answered exactly
    without a sweep are completed immediately — from the LRU **row
    cache** of previously computed distance rows (``row_cache_size``
    rows per semiring; repeated point-to-point traffic costs one lookup)
    or, with ``n_landmarks > 0``, from the **landmark oracle**
    (serve/oracle.py) when its triangle-inequality bounds certify the
    answer.  Both tiers are bit-identical to the sweep they avoid;
    uncertified answers are never served.

    **Bucketed batching (the fallback):** uncertified misses queue in
    FIFO buckets keyed by (query kind, predicted-sweep-count bin) — the
    landmark eccentricity bound predicts how many sweeps a source needs,
    so one deep-BFS query doesn't pad-waste a micro-batch of shallow
    ones (the length-bucketed batching idiom).  :meth:`flush` drains up
    to ``max_batch`` queries in global FIFO order (compat path — the
    attic LM ``ServingEngine`` tick uses it); :meth:`tick` applies the
    deadline-aware policy instead: a bucket flushes when it is full,
    when its earliest deadline minus the EWMA-estimated flush time
    leaves no headroom, or when its head has waited ``max_wait``.
    Queries whose deadline already passed when their batch forms are
    surfaced as ``expired`` (never silently dropped, never computed).

    Each flush runs at most one boolean, one tropical, and one
    counting/centrality micro-batch through the shared semiring sweep
    layer, exactly like decode steps amortize across KV slots; computed
    rows feed the row cache.  ``GraphQuery(analytics=...)`` requests
    micro-batch into one centrality run per flush, and the whole-graph
    betweenness vector is built once and served from cache.

    Pass ``mesh`` to scale flushes past one device: micro-batches of at
    least ``sharded_threshold`` queries route through the semiring-generic
    sharded executor (``core/distributed.py::sharded_apsp`` — sources
    sharded over the mesh's data axes, the operand optionally over
    ``model``), whose results are bit-identical to the single-device
    engines; smaller flushes stay on the single-device path where the
    collective overhead isn't worth it.

    Completed queries land in ``completed``, bounded to the most recent
    ``completed_retention`` entries; long-running loops should consume
    results via :meth:`drain_completed` (returns and clears) so nothing
    is lost to the retention cap.  ``clock`` injects a time source
    (default ``time.monotonic``) — deadline tests and the open-loop load
    benchmark drive a virtual clock through it.
    """

    def __init__(self, graph, *,
                 config: Optional[EngineConfig] = None,
                 weights=None,
                 weighted_config: Optional[WeightedConfig] = None,
                 max_batch: int = 32,
                 mesh=None,
                 sharded_threshold: int = 16,
                 sharded_config: Optional[ShardedConfig] = None,
                 sharded_weighted_config: Optional[ShardedConfig] = None,
                 centrality_config: Optional[CentralityConfig] = None,
                 n_landmarks: int = 0,
                 landmark_strategy: str = "mixed",
                 oracle: Optional[DistanceOracle] = None,
                 row_cache_size: int = 128,
                 completed_retention: Optional[int] = 4096,
                 max_wait: Optional[float] = None,
                 deadline_safety: float = 2.0,
                 clock: Callable[[], float] = time.monotonic):
        batch = max(8, ((max_batch + 7) // 8) * 8)
        if batch > 128:  # EngineConfig: above one push tile, multiple of 128
            batch = ((batch + 127) // 128) * 128
        self.config = config or EngineConfig(source_batch=batch)
        # per-flush latency cap: honored even with an explicit config (the
        # source tile stays config.source_batch wide; short flushes pad)
        self.max_batch = min(max_batch, self.config.source_batch)
        if hasattr(graph, "view") and weights is not None:
            raise ValueError(
                "weights= with a DynamicCSRGraph is ambiguous — a static "
                "weight array cannot track mutations; build the dynamic "
                "graph with weights instead")
        self.graph_source = graph
        self._base_weights = weights
        self._build_operands()
        # weighted queries ride the same kernel-path resolution as the
        # boolean engine: both semirings dispatch Pallas kernels through
        # the registry when the config (or TPU detection) says so
        self.weighted_config = weighted_config or \
            WeightedConfig(source_batch=min(self.config.source_batch, 128),
                           use_kernel=self.config.use_kernel)
        self.mesh = mesh
        self.sharded_threshold = max(1, sharded_threshold)
        self._sharded_cfg = {
            "boolean": sharded_config or
            ShardedConfig(semiring="boolean", mode="dense",
                          use_kernel=self.config.use_kernel),
            "tropical": sharded_weighted_config or
            ShardedConfig(semiring="tropical", mode="dense",
                          use_kernel=self.config.use_kernel),
        }
        self._sharded_ops: Dict[str, ShardedOperands] = {}
        self.sharded_flushes = 0
        self.centrality_config = centrality_config or CentralityConfig(
            source_batch=min(self.config.source_batch, 128),
            use_kernel=self.config.use_kernel)
        # betweenness is a whole-graph analytic: computed once (sharded
        # when a mesh is configured), then served from this cache
        self._betweenness: Optional[np.ndarray] = None
        # --- serving tier ----------------------------------------------
        self._clock = clock
        # the oracle is lazily (re)built by the `oracle` property so an
        # epoch invalidation can drop it without paying the label-table
        # sweeps until the next query that would consult it
        self._landmark_strategy = landmark_strategy
        if oracle is not None:
            self._oracle: Optional[DistanceOracle] = oracle
            self._oracle_n_landmarks = oracle.n_landmarks
        else:
            self._oracle = None
            self._oracle_n_landmarks = n_landmarks
        # LRU of exact distance rows keyed (kind, source); every sweep
        # feeds it, so a hot source pays one sweep ever
        self.row_cache_size = max(0, row_cache_size)
        self._row_cache: "OrderedDict[Tuple[str, int], np.ndarray]" = \
            OrderedDict()
        # FIFO buckets keyed (kind, predicted-sweep bin); _seq preserves
        # global submit order for the compat flush() drain
        self.buckets: "OrderedDict[Tuple[str, int], deque]" = OrderedDict()
        self._seq = 0
        self.max_wait = max_wait
        self.deadline_safety = deadline_safety
        self._flush_est = 0.02   # EWMA of sweep-flush seconds
        self.completed_retention = completed_retention
        self.completed: List[GraphQuery] = []
        # serving counters (totals since construction)
        self.cache_hits = 0
        self.oracle_hits = 0
        self.sweep_served = 0
        self.expired_count = 0
        self.n_submitted = 0
        self.n_completed_total = 0
        self.epoch_invalidations = 0

    # -- epoch freshness ---------------------------------------------------

    def _build_operands(self) -> None:
        """(Re)prepare engine operands from the current graph content.

        ``prepare_graph``/``prepare_weighted`` duck-type dynamic graphs
        (merged view + content epoch); for a weighted dynamic graph the
        lane weights come from its ``view_weights()``.
        """
        g = self.graph_source
        self.prepared: PreparedGraph = prepare_graph(g)
        if self._base_weights is not None:
            self.prepared_weighted: Optional[PreparedWeightedGraph] = \
                prepare_weighted(g, self._base_weights)
            self._weights = self._base_weights
        elif getattr(g, "weighted", False) and hasattr(g, "view_weights"):
            self.prepared_weighted = prepare_weighted(g)
            self._weights = g.view_weights()
        else:
            self.prepared_weighted = None
            self._weights = None

    @property
    def oracle(self) -> Optional[DistanceOracle]:
        """Landmark oracle for the *current* epoch, built on demand."""
        if self._oracle is None and self._oracle_n_landmarks > 0:
            self._oracle = DistanceOracle(
                self.prepared, n_landmarks=self._oracle_n_landmarks,
                strategy=self._landmark_strategy, config=self.config)
        return self._oracle

    def _ensure_fresh(self) -> None:
        """Invalidate every cached artifact when the graph has mutated.

        Compares the source graph's content ``epoch`` against the epoch
        ``self.prepared`` was built at (static graphs are always epoch
        0, so this is a no-op for them).  On mismatch: re-prepare the
        engine operands, clear the LRU row cache, the cached
        betweenness vector and the sharded operands, and drop the
        oracle (its landmark label tables rebuild lazily against the
        fresh ``PreparedGraph`` on next touch).  Called at the top of
        every entry point (``submit``/``flush``/``tick``), so no
        admission or batch execution can ever read a stale cache.
        """
        if int(getattr(self.graph_source, "epoch", 0)) == \
                self.prepared.epoch:
            return
        self._build_operands()
        self._row_cache.clear()
        self._betweenness = None
        self._sharded_ops.clear()
        self._oracle = None
        self.epoch_invalidations += 1

    def _sharded_operands(self, semiring: str) -> ShardedOperands:
        """Lazy per-semiring ShardedOperands (dense/partitioned operands
        built and device_put once, reused every sharded flush).  On a
        mesh without vertex sharding the padded size matches the
        single-device operands, so those are handed over instead of
        materializing a second O(n_pad^2) dense copy."""
        if semiring not in self._sharded_ops:
            cfg = self._sharded_cfg[semiring]
            dense_op = None
            if "model" not in self.mesh.axis_names or \
                    dict(self.mesh.shape).get("model", 1) == 1:
                if semiring == "boolean" and cfg.need_dense:
                    dense_op = self.prepared.adj
                elif semiring == "tropical" and cfg.need_dense:
                    dense_op = self.prepared_weighted.wdense
            self._sharded_ops[semiring] = prepare_sharded(
                self.prepared.graph, self.mesh,
                weights=self._weights if semiring == "tropical" else None,
                config=cfg, dense_op=dense_op)
        return self._sharded_ops[semiring]

    def _route_sharded(self, n_queries: int) -> bool:
        return self.mesh is not None and \
            n_queries >= self.sharded_threshold

    # -- admission ---------------------------------------------------------

    @obs.spanned("serve.submit")
    def submit(self, query: GraphQuery):
        """Validate, then answer from the cache/oracle tier or enqueue.

        Certified answers (row cache, landmark oracle) complete *at
        submit* — they never occupy a sweep batch.  Everything else
        lands in the FIFO bucket for its (kind, predicted-sweeps) key.
        """
        self._ensure_fresh()
        n = self.prepared.graph.n_nodes
        if not 0 <= query.source < n:
            raise ValueError(f"source {query.source} not in [0, {n})")
        if query.target is not None and not 0 <= query.target < n:
            raise ValueError(f"target {query.target} not in [0, {n})")
        if query.analytics is not None:
            if query.weighted:
                raise ValueError("analytics queries are unweighted "
                                 "(counting/boolean semiring)")
            unknown = set(query.analytics) - set(MEASURES)
            if unknown:
                raise ValueError(f"unknown analytics {sorted(unknown)}; "
                                 f"available: {MEASURES}")
        if query.k_nearest is not None:
            if query.k_nearest < 1:
                raise ValueError(f"k_nearest must be >= 1, "
                                 f"got {query.k_nearest}")
            if query.target is not None or query.analytics is not None \
                    or query.weighted:
                raise ValueError("k_nearest queries are unweighted and "
                                 "exclusive of target=/analytics=")
        if query.weighted and self.prepared_weighted is None:
            raise ValueError(
                "weighted query on a GraphService built without weights=")
        now = self._clock()
        query.t_submit = now
        query.t_deadline = now + query.deadline \
            if query.deadline is not None else math.inf
        query._seq = self._seq
        self._seq += 1
        self.n_submitted += 1
        if self._try_serve_cached(query, now):
            return
        self.buckets.setdefault(self._bucket_key(query),
                                deque()).append(query)

    def _try_serve_cached(self, q: GraphQuery, now: float) -> bool:
        """Row-cache then landmark-oracle admission; True == completed."""
        if q.analytics is not None:
            return False
        kind = "weighted" if q.weighted else "unweighted"
        row = self._row_cache.get((kind, q.source))
        if row is not None:
            self._row_cache.move_to_end((kind, q.source))
            self._fill_from_row(q, row)
            self.cache_hits += 1
            q.certified = True
            self._complete(q, "cache", now)
            return True
        if self.oracle is None or q.weighted:
            return False
        if q.target is not None:
            ans = self.oracle.query(q.source, q.target)
            if not ans.exact:
                return False
            q.hops = ans.hops
        elif q.k_nearest is not None:
            nearest = self.oracle.top_k(q.source, q.k_nearest)
            if nearest is None:
                return False
            q.nearest = nearest
        else:
            lrow = self.oracle.landmark_row(q.source)
            if lrow is None:
                return False
            q.dist = np.array(lrow)
        self.oracle_hits += 1
        q.certified = True
        self._complete(q, "oracle", now)
        return True

    def _fill_from_row(self, q: GraphQuery, row: np.ndarray) -> None:
        """Answer any non-analytics query kind from an exact dist row."""
        if q.target is not None:
            if q.weighted:
                q.cost = float(row[q.target])
            else:
                q.hops = int(row[q.target])
        elif q.k_nearest is not None:
            q.nearest = select_top_k(row, q.source, q.k_nearest)
        else:
            q.dist = np.array(row)

    def _cache_row(self, kind: str, source: int, row: np.ndarray) -> None:
        if self.row_cache_size <= 0:
            return
        self._row_cache[(kind, int(source))] = np.asarray(row)
        self._row_cache.move_to_end((kind, int(source)))
        while len(self._row_cache) > self.row_cache_size:
            self._row_cache.popitem(last=False)

    def _bucket_key(self, q: GraphQuery) -> Tuple[str, int]:
        """(kind, predicted-sweep bin): queries expected to converge in a
        similar sweep count batch together, so a deep-BFS straggler can't
        stretch the while_loop of a shallow batch (pad waste)."""
        if q.analytics is not None:
            return ("analytics", 0)
        if q.weighted:
            return ("weighted", 0)
        bin_ = self.oracle.predicted_sweeps(q.source).bit_length() \
            if self.oracle is not None else 0
        return ("unweighted", bin_)

    def _complete(self, q: GraphQuery, served_by: str, now: float) -> None:
        q.served_by = served_by
        q.t_done = now
        self.completed.append(q)
        self.n_completed_total += 1
        if self.completed_retention is not None and \
                len(self.completed) > self.completed_retention:
            del self.completed[: len(self.completed)
                               - self.completed_retention]

    def drain_completed(self) -> List[GraphQuery]:
        """Return all retained completed queries and clear the buffer —
        the consumption API for long-running serving loops (retention
        only bounds callers that never drain)."""
        out = self.completed
        self.completed = []
        return out

    def pending(self) -> int:
        return sum(len(b) for b in self.buckets.values())

    # -- flush policy ------------------------------------------------------

    def flush(self) -> List[GraphQuery]:
        """Serve up to ``max_batch`` pending queries in global FIFO
        order regardless of buckets or deadlines; returns them.  The
        unconditional drain — the attic ``ServingEngine.step`` calls it
        every tick; :meth:`tick` is the deadline/size-aware
        alternative."""
        self._ensure_fresh()
        batch = self._take_global(self.max_batch)
        return self._serve(batch)

    @obs.spanned("serve.tick")
    def tick(self) -> List[GraphQuery]:
        """Deadline-aware flush: serve ONE ripe bucket (FIFO within it),
        or nothing if no bucket is ripe.

        A bucket is ripe when it is full (``max_batch``), when its
        earliest deadline leaves less headroom than ``deadline_safety``
        x the EWMA flush-time estimate, or when its head query has
        waited ``max_wait``.  Serving a single bucket keeps the
        micro-batch homogeneous in predicted sweep count — the whole
        point of bucketing.  Ripest = earliest deadline, then oldest.
        """
        self._ensure_fresh()
        now = self._clock()
        headroom = self.deadline_safety * self._flush_est
        best_key, best_rank = None, None
        for key, bucket in self.buckets.items():
            if not bucket:
                continue
            dl = min(q.t_deadline for q in bucket)
            ripe = (len(bucket) >= self.max_batch
                    or dl - now <= headroom
                    or (self.max_wait is not None
                        and now - bucket[0].t_submit >= self.max_wait))
            if not ripe:
                continue
            rank = (dl, bucket[0]._seq)
            if best_rank is None or rank < best_rank:
                best_key, best_rank = key, rank
        if best_key is None:
            return []
        bucket = self.buckets[best_key]
        batch = [bucket.popleft()
                 for _ in range(min(len(bucket), self.max_batch))]
        return self._serve(batch)

    def _take_global(self, limit: int) -> List[GraphQuery]:
        """Pop up to ``limit`` queries in global submit order (merge the
        per-bucket FIFOs by sequence number)."""
        batch: List[GraphQuery] = []
        while len(batch) < limit:
            best = None
            for key, bucket in self.buckets.items():
                if bucket and (best is None
                               or bucket[0]._seq < self.buckets[best][0]._seq):
                    best = key
            if best is None:
                break
            batch.append(self.buckets[best].popleft())
        return batch

    # -- batch execution ---------------------------------------------------

    def _serve(self, batch: List[GraphQuery]) -> List[GraphQuery]:
        if not batch:
            return []
        now = self._clock()
        live: List[GraphQuery] = []
        for q in batch:
            if q.t_deadline < now:
                # deadline already blown: surface, don't compute — an
                # expired query must neither vanish nor pad a live batch
                q.expired = True
                self.expired_count += 1
                self._complete(q, "expired", now)
            else:
                live.append(q)
        if not live:
            return batch
        with obs.span("serve.flush", rows=len(live),
                      tile=self.config.source_batch,
                      wait_ms=1e3 * (now - min(q.t_submit for q in live))):
            self._flush_live(live)
        return batch

    @staticmethod
    def _rows_to_host(dist) -> np.ndarray:
        """A flush's distance rows, waited for on the device, then copied
        to the host."""
        with obs.span("serve.flush.wait"):
            jax.block_until_ready(dist)
        with obs.span("serve.flush.copy"):
            return np.asarray(dist)

    def _flush_live(self, live: List[GraphQuery]) -> None:
        """Run the sweep micro-batches of one flush and complete its
        queries."""
        # measured with the injected clock so the EWMA below shares a
        # time scale with deadlines/ripeness under a virtual clock
        t0 = self._clock()
        analytics = [q for q in live if q.analytics is not None]
        unweighted = [q for q in live
                      if not q.weighted and q.analytics is None]
        weighted = [q for q in live if q.weighted]
        if unweighted:
            sources = np.asarray([q.source for q in unweighted], np.int32)
            if self._route_sharded(len(unweighted)):
                dist = sharded_apsp(self._sharded_operands("boolean"),
                                    sources).dist
                self.sharded_flushes += 1
                served_by = "sharded"
            else:
                (_, dist, _), = apsp_engine_blocks(self.prepared, sources,
                                                   config=self.config)
                served_by = "sweep"
            dist = self._rows_to_host(dist)
            with obs.span("serve.flush.fill"):
                for row, q in zip(dist, unweighted):
                    self._fill_from_row(q, row)
                    self._cache_row("unweighted", q.source, row)
                    q.served_by = served_by
        if weighted:
            sources = np.asarray([q.source for q in weighted], np.int32)
            if self._route_sharded(len(weighted)):
                dist = sharded_apsp(self._sharded_operands("tropical"),
                                    sources).dist
                self.sharded_flushes += 1
                served_by = "sharded"
            else:
                dist = weighted_apsp(self.prepared_weighted, sources=sources,
                                     config=self.weighted_config).dist
                served_by = "sweep"
            dist = self._rows_to_host(dist)
            with obs.span("serve.flush.fill"):
                for row, q in zip(dist, weighted):
                    self._fill_from_row(q, row)
                    self._cache_row("weighted", q.source, row)
                    q.served_by = served_by
        if analytics:
            self._flush_analytics(analytics)
            for q in analytics:
                q.served_by = "sweep"
        self.sweep_served += len(live)
        # EWMA of the wall cost of one sweep flush — feeds tick()'s
        # deadline-headroom estimate
        self._flush_est = 0.5 * self._flush_est + \
            0.5 * (self._clock() - t0)
        now = self._clock()
        for q in live:
            q.t_done = now
            self.completed.append(q)
            self.n_completed_total += 1
        if self.completed_retention is not None and \
                len(self.completed) > self.completed_retention:
            del self.completed[: len(self.completed)
                               - self.completed_retention]

    def _flush_analytics(self, queries: List[GraphQuery]) -> None:
        """Serve one micro-batch of centrality queries: all per-source
        measures ride ONE batched multi-source run (the analytics
        analogue of the distance micro-batch); betweenness comes from
        the per-service cache, built on first demand — through the
        sharded executor when the service has a mesh."""
        per_source = set()
        want_bc = False
        for q in queries:
            for m in q.analytics:
                if m == "betweenness":
                    want_bc = True
                else:
                    per_source.add(m)
        results: Dict[int, Dict[str, float]] = {
            id(q): {} for q in queries}
        # one batched run over only the queries that need per-source
        # measures (betweenness-only queries are served from the cache),
        # reusing the service's prepared operands and calibration cache
        ps_queries = [q for q in queries
                      if set(q.analytics) - {"betweenness"}]
        if ps_queries:
            sources = np.asarray([q.source for q in ps_queries], np.int32)
            res = centrality(self.prepared, sources,
                             measures=tuple(sorted(per_source)),
                             config=self.centrality_config)
            if res.closeness is not None:
                for i, q in enumerate(ps_queries):
                    results[id(q)]["closeness"] = float(res.closeness[i])
            if res.harmonic is not None:
                for i, q in enumerate(ps_queries):
                    results[id(q)]["harmonic"] = float(res.harmonic[i])
            if res.eccentricity is not None:
                for i, q in enumerate(ps_queries):
                    results[id(q)]["eccentricity"] = \
                        int(res.eccentricity[i])
        if want_bc:
            if self._betweenness is None:
                n = self.prepared.graph.n_nodes
                self._betweenness = betweenness(
                    self.prepared, config=self.centrality_config,
                    mesh=self.mesh if (self.mesh is not None and
                                       n >= self.sharded_threshold)
                    else None)
                if self.mesh is not None and \
                        n >= self.sharded_threshold:
                    self.sharded_flushes += 1
            for q in queries:
                if "betweenness" in q.analytics:
                    results[id(q)]["betweenness"] = \
                        float(self._betweenness[q.source])
        for q in queries:
            q.analytics_result = {m: results[id(q)][m]
                                  for m in q.analytics}
