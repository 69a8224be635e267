"""Unified ``dawn`` facade: one handle, every semiring, static or mutable.

The caller-visible surface of the reproduction used to be four parallel
config dataclasses and one entry point per semiring (``apsp_engine`` /
``weighted_apsp`` / ``counting_apsp`` / ``sharded_apsp``).  This module
replaces that spread with a single verb:

    import repro as dawn

    h = dawn.prepare(graph)                     # static CSRGraph
    d = h.sssp(0)                               # one dist row
    res = h.apsp(semiring="boolean")            # batched engine result
    cen = h.centrality(measures=("closeness",))
    svc = h.serve(n_landmarks=16)               # tiered GraphService

    h = dawn.prepare(dyn)                       # DynamicCSRGraph
    h.insert_edges([u], [v])                    # mutation passthrough
    d = h.sssp(0)                               # fresh epoch, same call

Every query method takes ``semiring=`` ("boolean" / "tropical" /
"counting") and ``mesh=`` (route through the sharded executor) keywords;
tuning knobs come from one :class:`repro.core.options.SweepOptions`
passed to :func:`prepare` (or plain keywords forwarded to it).  The old
config dataclasses survive as thin subclasses — the handle projects the
shared options onto whichever engine a call dispatches to via
``SweepOptions.to``.

The handle is epoch-aware: prepared operands are built lazily per
semiring and rebuilt automatically whenever the underlying
:class:`repro.graph.dynamic.DynamicCSRGraph` has mutated since they
were prepared, so "same query, now on a mutable graph" is exactly the
same call.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence, Union

import numpy as np

from . import obs
from .core.autotune import TuningPlan, build_plan
from .core.centrality import (MEASURES, CentralityConfig, CentralityResult,
                              centrality as _centrality)
from .core.centrality import counting_apsp as _counting_apsp
from .core.distributed import ShardedConfig, prepare_sharded
from .core.distributed import sharded_apsp as _sharded_apsp
from .core.engine import EngineConfig, prepare_graph
from .core.engine import apsp_engine as _apsp_engine
from .core.incremental import IncrementalSSSP
from .core.options import SweepOptions
from .core.weighted import WeightedConfig, prepare_weighted
from .core.weighted import weighted_apsp as _weighted_apsp
from .graph.csr import CSRGraph
from .graph.dynamic import DynamicCSRGraph

SEMIRING_NAMES = ("boolean", "tropical", "counting")


class DawnGraph:
    """Prepared-graph handle returned by :func:`prepare`.

    Query methods (``sssp`` / ``apsp`` / ``centrality``) lazily build
    and cache the per-semiring prepared operands; on a mutable graph
    every call first checks the content epoch and re-prepares when the
    graph has changed.  ``serve`` hands the *source* graph to
    :class:`repro.serve.GraphService`, whose own epoch guard covers the
    serving-tier caches.
    """

    def __init__(self, graph: Union[CSRGraph, DynamicCSRGraph], *,
                 weights=None, options: Optional[SweepOptions] = None):
        if isinstance(graph, DynamicCSRGraph) and weights is not None:
            raise ValueError(
                "weights= with a DynamicCSRGraph is ambiguous — build the "
                "dynamic graph with weights instead")
        self.graph = graph
        self.options = options or SweepOptions()
        self._weights = weights
        self._pg = None          # PreparedGraph (boolean/counting)
        self._pw = None          # PreparedWeightedGraph (tropical)
        self._sharded = {}       # semiring -> ShardedOperands
        self._sharded_mesh = None
        self._sharded_epoch = -1

    # -- epoch-aware operand cache ----------------------------------------

    @property
    def epoch(self) -> int:
        return int(getattr(self.graph, "epoch", 0))

    @property
    def mutable(self) -> bool:
        return isinstance(self.graph, DynamicCSRGraph)

    def _lane_weights(self):
        if self._weights is not None:
            return self._weights
        if self.mutable and self.graph.weighted:
            return self.graph.view_weights()
        return None

    def prepared(self):
        """Current-epoch :class:`PreparedGraph` (boolean operands)."""
        if self._pg is None or self._pg.epoch != self.epoch:
            self._pg = prepare_graph(self.graph)
        return self._pg

    def prepared_weighted(self):
        """Current-epoch :class:`PreparedWeightedGraph` (tropical)."""
        w = self._lane_weights()
        if w is None:
            raise ValueError(
                "tropical semiring needs weights: prepare(graph, weights=...) "
                "or a weighted DynamicCSRGraph")
        if self._pw is None or self._pw.epoch != self.epoch:
            self._pw = prepare_weighted(self.graph) if self.mutable \
                else prepare_weighted(self.graph, w)
        return self._pw

    def _sharded_operands(self, semiring: str, mesh):
        if mesh is not self._sharded_mesh or self._sharded_epoch != \
                self.epoch:
            self._sharded = {}
            self._sharded_mesh = mesh
            self._sharded_epoch = self.epoch
        if semiring not in self._sharded:
            # "sparse" names the same form in every engine; any other
            # mode runs the sharded executor's dense form
            mode = "sparse" if self.options.mode == "sparse" else "dense"
            cfg = self.options.to(
                ShardedConfig, lenient=True, semiring=semiring, mode=mode)
            g = self.graph.view() if self.mutable else self.graph
            self._sharded[semiring] = prepare_sharded(
                g, mesh, weights=self._lane_weights()
                if semiring == "tropical" else None, config=cfg)
        return self._sharded[semiring]

    # -- mutation passthrough (DynamicCSRGraph only) -----------------------

    def _dynamic(self) -> DynamicCSRGraph:
        if not self.mutable:
            raise TypeError(
                "graph is a static CSRGraph; prepare(DynamicCSRGraph...) "
                "for mutation support")
        return self.graph

    def insert_edges(self, src, dst, weights=None) -> int:
        return self._dynamic().insert_edges(src, dst, weights)

    def delete_edges(self, src, dst) -> int:
        return self._dynamic().delete_edges(src, dst)

    def compact(self) -> None:
        self._dynamic().compact()

    # -- queries -----------------------------------------------------------

    def _check_semiring(self, semiring: str) -> None:
        if semiring not in SEMIRING_NAMES:
            raise ValueError(
                f"unknown semiring {semiring!r}; one of {SEMIRING_NAMES}")

    def apsp(self, sources: Optional[Sequence[int]] = None, *,
             semiring: str = "boolean", mesh=None,
             checkpoint_dir: Optional[str] = None,
             checkpoint_interval: int = 1,
             chunk_size: Optional[int] = None, resume: bool = True,
             on_chunk=None):
        """Batched multi-source shortest paths (default: all sources).

        Returns the dispatched engine's native result — ``ApspResult``
        (boolean), ``WeightedApspResult`` (tropical), ``CountingResult``
        (counting) or ``ShardedApspResult`` (any semiring + ``mesh=``) —
        all carrying ``.dist`` plus sweep counters.

        ``checkpoint_dir=`` routes through the resumable-job layer
        (:func:`repro.core.jobs.run_sweep_job`): the run is chunked into
        ``chunk_size`` source tiles, checkpointed every
        ``checkpoint_interval`` chunks, and a rerun of the same call
        resumes from the newest intact checkpoint (``resume=False``
        starts over).  Returns a :class:`repro.core.jobs.JobResult`
        carrying the resume counters (``chunks_restored``,
        ``restored_step``, ``corrupt_skipped``, ...) alongside the
        distances.
        """
        self._check_semiring(semiring)
        n_sources = self.graph.n_nodes if sources is None else len(sources)
        with obs.span("apsp", semiring=semiring, n_sources=n_sources):
            if checkpoint_dir is not None or on_chunk is not None:
                from .core.jobs import run_sweep_job
                return run_sweep_job(
                    self.graph, sources, workload=semiring,
                    weights=self._lane_weights()
                    if semiring == "tropical" else None,
                    mesh=mesh, options=self.options, chunk_size=chunk_size,
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_interval=checkpoint_interval, resume=resume,
                    on_chunk=on_chunk)
            if mesh is not None:
                # config is baked into the prepared operands
                # (_sharded_operands)
                return _sharded_apsp(self._sharded_operands(semiring, mesh),
                                     sources)
            if semiring == "boolean":
                return _apsp_engine(self.prepared(), sources,
                                    config=self.options.to(EngineConfig,
                                                           lenient=True))
            if semiring == "tropical":
                return _weighted_apsp(self.prepared_weighted(),
                                      sources=sources,
                                      config=self.options.to(WeightedConfig,
                                                             lenient=True))
            return _counting_apsp(self.prepared(), sources,
                                  config=self.options.to(CentralityConfig,
                                                         lenient=True))

    def sssp(self, source: int, *, semiring: str = "boolean",
             mesh=None) -> np.ndarray:
        """One distance row from ``source`` — int32 hops with -1 for
        unreachable (boolean/counting), float32 with +inf (tropical)."""
        res = self.apsp([int(source)], semiring=semiring, mesh=mesh)
        return np.asarray(res.dist[0])

    def centrality(self, sources: Optional[Sequence[int]] = None, *,
                   measures: Sequence[str] = MEASURES,
                   mesh=None) -> CentralityResult:
        """Batched centrality analytics over the counting semiring."""
        return _centrality(self.prepared(), sources, measures=measures,
                           config=self.options.to(CentralityConfig,
                                                  lenient=True),
                           mesh=mesh)

    def incremental(self, sources, *, config=None) -> IncrementalSSSP:
        """Streaming repair driver bound to this handle's dynamic graph
        (frontier-seeded incremental BFS/SSSP — core/incremental.py)."""
        g = self._dynamic()
        if config is None:
            config = self.options.to(
                WeightedConfig if g.weighted else EngineConfig,
                lenient=True)
        return IncrementalSSSP(g, sources, config=config)

    # -- autotuning --------------------------------------------------------

    @property
    def tuning(self) -> Optional[TuningPlan]:
        """The TuningPlan cached on this handle (None = untuned)."""
        return self.options.tuning

    def tune(self, *, use_hlo: bool = True, save=None,
             profile=None) -> TuningPlan:
        """Build a roofline :class:`TuningPlan` for this graph, cache it
        on the handle (every later query consults it — tile sizes, the
        fused gate, and the constants of ``mode="auto"``'s cost model),
        and optionally ``save`` it for reproducible reruns
        (``prepare(g, tuning="plan.json")``)."""
        plan = build_plan(self.prepared(), weights=self._lane_weights(),
                          profile=profile, use_hlo=use_hlo)
        if save is not None:
            plan.save(save)
        self.options = dataclasses.replace(self.options, tuning=plan)
        self._sharded = {}       # baked configs must pick the plan up
        return plan

    def serve(self, *, mesh=None, **kwargs):
        """Construct a tiered :class:`repro.serve.GraphService` over the
        source graph (epoch-guarded when the graph is dynamic).  Keyword
        arguments pass through (``n_landmarks=``, ``max_batch=``,
        ``clock=``, ...)."""
        from .serve.engine import GraphService
        kwargs.setdefault("config",
                          self.options.to(EngineConfig, lenient=True))
        if self._weights is not None:
            kwargs.setdefault("weights", self._weights)
        return GraphService(self.graph, mesh=mesh, **kwargs)


def prepare(graph: Union[CSRGraph, DynamicCSRGraph], *, weights=None,
            options: Optional[SweepOptions] = None, **opts) -> DawnGraph:
    """Entry point of the facade: wrap a graph in a :class:`DawnGraph`.

    ``options=`` takes a ready :class:`SweepOptions`; any extra keywords
    construct one (``prepare(g, source_batch=64, use_kernel=False)``).
    ``weights=`` attaches static edge weights for the tropical semiring
    (a weighted :class:`DynamicCSRGraph` carries its own).
    ``tuning=`` accepts a :class:`TuningPlan` or the path of a saved one
    (loaded with the backend-fingerprint check), so that a rerun uses the
    same tiles and cost constants; build one with :meth:`DawnGraph.tune`.
    """
    if options is not None and opts:
        raise ValueError("pass options= or plain keywords, not both")
    if isinstance(opts.get("tuning"), (str, os.PathLike)):
        opts["tuning"] = TuningPlan.load(opts["tuning"])
    return DawnGraph(graph, weights=weights,
                     options=options or SweepOptions(**opts))
