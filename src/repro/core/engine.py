"""Direction-optimizing batched APSP engine over the semiring sweep layer.

The paper's all-pairs bound O(S_wcc * E_wcc) is only reachable when every
sweep runs in its cheapest *form*.  The boolean semiring has three
equivalent forms (core/sweep.py::boolean_forms) with very different cost
profiles:

  PUSH   — dense boolean GEMM (paper Alg. 1 / BOVM).  On TPU this is the
           MXU ``fused_sweep`` kernel whose tile-skip tables make its cost
           proportional to the *live* (frontier x unreached) tile fraction.
  PULL   — bit-packed AND/OR over in-neighbour words (paper's CSC BOVM,
           §3.2).  Reads 32 nodes per uint32 lane; cost proportional to the
           unreached tile fraction but independent of frontier size.
  SPARSE — edge-parallel gather/scatter over CSR lanes (paper Alg. 2 /
           SOVM).  Cost proportional to the padded edge count, independent
           of both occupancies.

This module tiles sources into MXU-aligned batches, runs each tile through
the shared :func:`repro.core.sweep.sweep_loop` driver, and picks the
cheapest form per sweep (direction-optimizing BFS in the style of Beamer's
push/pull switch, generalized to three forms).  Under ``mode="auto"`` a
``lax.switch`` at every sweep runs the form the occupancy cost model in
:func:`sweep_costs` prices lowest, on every backend.  The signals are
exactly the scalar-prefetch tables the Pallas push kernel computes per
sweep, so the heuristic is free, and being a function of the state alone
it makes ``direction_counts`` reproducible.  ``mode="<form>"`` pins one
form for the whole batch (no stats, no switch).

All three sweeps operate on identical padded state (frontier (S, n_pad)
int8, dist (S, n_pad) int32), so switching costs nothing but the branch.
The weighted analogue of this driver lives in core/weighted.py
(``weighted_apsp``) and reuses the same cost model over the tropical
forms.

Thresholds and cost constants are documented in docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Iterator, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..graph.csr import CSRGraph
from . import autotune
from . import sweep as S
from .frontier import UNREACHED, one_hot_frontier
from .options import SweepOptions
from .sweep import DIRECTION_NAMES, PULL, PUSH, SPARSE, SweepState


@dataclasses.dataclass(frozen=True)
class EngineConfig(SweepOptions):
    """Static boolean-engine parameters (a :class:`SweepOptions`
    subclass, hashable: used as a jit static arg).

    Cost-model units (see docs/ARCHITECTURE.md for their derivation):
      c_push   — per dense element in a live (i, j, k) push tile (MXU MAC)
      c_pull   — per uint32 word scanned by the pull sweep (VPU bitwise op;
                 one word covers 32 nodes, so the per-element cost is
                 c_pull / 32)
      c_sparse — per padded CSR edge lane (gather + random scatter)
    """
    # cost model
    c_push: float = 1.0
    c_pull: float = 8.0
    c_sparse: float = 8.0
    pull_chunk: int = 512            # ref pull: nodes per lax.map chunk

    _mode_names = DIRECTION_NAMES    # push | pull | sparse


class SweepStats(NamedTuple):
    """Per-sweep occupancy signals (traced scalars, computed in-loop)."""
    live_tile_frac: jax.Array   # fraction of (i,j,k) push tiles doing work
    o_occ_frac: jax.Array       # fraction of output tiles with unreached


class ApspResult(NamedTuple):
    dist: jax.Array              # (S, n) int32, -1 unreachable
    sweeps: jax.Array            # int32 — max sweeps over batches
    direction_counts: jax.Array  # (3,) int32 — push/pull/sparse sweeps run
    edges_touched: jax.Array     # float32 — Eq. 10 useful-work counter


@dataclasses.dataclass
class PreparedGraph:
    """Device-resident operands shared by all three sweep forms.

    The dense push operand and the bit-packed pull operand are O(n_pad^2)
    and built lazily on first use: a run whose resolved direction never
    dispatches them (e.g. ``mode='sparse'`` on a large road network) only
    ever touches the O(m) CSR lanes and scales to graphs the dense forms
    can't hold.
    """
    graph: CSRGraph
    deg: jax.Array        # (n_pad,) float32 out-degrees (0 on pad)
    n_pad: int
    # content epoch of the source graph at prepare time (0 for a static
    # CSRGraph) — staleness checks in serve/ and repro.api key on it
    epoch: int = 0
    # landmark label tables for the distance-oracle serving tier
    # (serve/oracle.py builds them with apsp_engine — the batched engine
    # IS the preprocessing pass — and caches them here so every oracle
    # over the same prepared graph shares one build):
    #   landmarks          (L,) int32 sorted vertex ids
    #   landmark_dist      (L, n) int32 forward rows d(landmark -> v)
    #   landmark_dist_rev  (L, n) int32 reverse rows d(v -> landmark)
    #                      (same array object as landmark_dist when the
    #                      graph is symmetric)
    #   landmark_key       build fingerprint (k, strategy) — a different
    #                      request rebuilds and overwrites
    landmarks: Optional[np.ndarray] = dataclasses.field(default=None,
                                                        repr=False)
    landmark_dist: Optional[np.ndarray] = dataclasses.field(default=None,
                                                            repr=False)
    landmark_dist_rev: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)
    landmark_key: Optional[tuple] = dataclasses.field(default=None,
                                                      repr=False)
    _adj: Optional[jax.Array] = dataclasses.field(default=None, repr=False)
    _adj_pull: Optional[jax.Array] = dataclasses.field(default=None,
                                                       repr=False)
    _rows: Optional[tuple] = dataclasses.field(default=None, repr=False)

    @property
    def adj(self) -> jax.Array:
        """(n_pad, n_pad) int8 dense adjacency (push operand)."""
        if self._adj is None:
            self._adj = self.graph.to_dense_padded(self.n_pad,
                                                   dtype=jnp.int8)
        return self._adj

    @property
    def adj_pull(self) -> jax.Array:
        """(n_pad, n_pad/32) uint32 packed in-neighbours (pull operand)."""
        if self._adj_pull is None:
            self._adj_pull = self.graph.to_pull_packed(self.n_pad,
                                                       adj=self._adj)
        return self._adj_pull

    @property
    def rows(self) -> Tuple[jax.Array, jax.Array]:
        """The sparse form's ``(row_src, row_dst)`` destination-row layout
        (:func:`repro.core.sweep.dst_rows`)."""
        if self._rows is None:
            g = self.graph
            self._rows = S.dst_rows(g.indptr_t, g.indices_t,
                                    n_real=g.n_nodes)
        return self._rows


def prepare_graph(g, *, align: int = 128) -> PreparedGraph:
    """Pad-size the graph and build the O(n) degree operand; the dense
    push/pull operands materialize lazily when a sweep form needs them.

    Accepts a plain :class:`CSRGraph` or a
    :class:`repro.graph.dynamic.DynamicCSRGraph` — the latter prepares
    its merged ``view()`` snapshot and records the content ``epoch`` so
    downstream caches can staleness-check against the live graph."""
    epoch = 0
    if hasattr(g, "view"):            # DynamicCSRGraph duck-type
        epoch = int(g.epoch)
        g = g.view()
    n_pad = g.n_padded(align)
    deg = jnp.zeros(n_pad, jnp.float32).at[: g.n_nodes].set(
        g.out_degrees().astype(jnp.float32))
    return PreparedGraph(graph=g, deg=deg, n_pad=n_pad, epoch=epoch)


# --------------------------------------------------------------------------
# heuristic: occupancy stats -> modelled sweep costs -> direction
# --------------------------------------------------------------------------

def frontier_stats(frontier: jax.Array, dist: jax.Array, *, bs: int,
                   bn: int, bk: int,
                   unreached: Optional[jax.Array] = None) -> SweepStats:
    """Tile-occupancy fractions — the same tables the push kernel prefetches.

    live(i, j, k) = f_occ[i, k] & o_occ[i, j]; its mean factorizes as
    E_i[ mean_k f_occ[i, :] * mean_j o_occ[i, :] ].

    ``unreached`` is the semiring's not-yet-settled mask; default is the
    boolean semiring's ``dist < 0`` (tropical passes ``isinf(dist)``).
    """
    s, n = frontier.shape
    gi, gj, gk = s // bs, n // bn, n // bk
    unr = (dist < 0) if unreached is None else unreached
    f_occ = jnp.any(frontier.reshape(gi, bs, gk, bk) != 0, axis=(1, 3))
    o_occ = jnp.any(unr.reshape(gi, bs, gj, bn), axis=(1, 3))
    f_row = jnp.mean(f_occ.astype(jnp.float32), axis=1)   # (gi,)
    o_row = jnp.mean(o_occ.astype(jnp.float32), axis=1)   # (gi,)
    return SweepStats(
        live_tile_frac=jnp.mean(f_row * o_row),
        o_occ_frac=jnp.mean(o_row),
    )


def sweep_costs(stats: SweepStats, *, n_pad: int, s: int, m_pad: int,
                cfg: EngineConfig) -> jax.Array:
    """Modelled cost of one sweep in each form -> (3,) float32."""
    words = n_pad // 32
    push = cfg.c_push * s * n_pad * n_pad * stats.live_tile_frac
    pull = cfg.c_pull * s * n_pad * words * stats.o_occ_frac
    sparse = jnp.float32(cfg.c_sparse * s * m_pad)
    return jnp.stack([push, pull, jnp.broadcast_to(sparse, ())])


def choose_direction(stats: SweepStats, *, n_pad: int, s: int, m_pad: int,
                     cfg: EngineConfig) -> jax.Array:
    """argmin of the modelled costs -> PUSH | PULL | SPARSE (traced int32)."""
    return jnp.argmin(
        sweep_costs(stats, n_pad=n_pad, s=s, m_pad=m_pad, cfg=cfg)
    ).astype(jnp.int32)


# --------------------------------------------------------------------------
# jitted per-batch driver (state + loop live in core/sweep.py)
# --------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_real", "n_pad", "m_pad",
                                    "max_steps", "use_kernel", "interpret",
                                    "forced_dir", "fused_steps"))
def _run_batch(adj, adj_pull, row_src, row_dst, deg, sources, n_valid, *,
               cfg: EngineConfig, n_real: int, n_pad: int, m_pad: int,
               max_steps: int, use_kernel: bool, interpret: bool,
               forced_dir: Optional[int],
               fused_steps: int = 0) -> SweepState:
    # n_valid is traced (not static): the serving loop flushes micro-batches
    # of whatever size is pending, and each distinct count must not retrace.
    # row_src/row_dst: the sparse form's destination rows
    # (PreparedGraph.rows); the cost model prices the sparse form by the
    # CSR's m_pad
    s = sources.shape[0]
    bs = min(s, 128)

    with obs.scope("batch.init"):
        f0 = one_hot_frontier(sources, n_pad, dtype=jnp.int8)
        # padded source rows (>= n_valid) start with an empty frontier and
        # a fully-visited dist: they do no work, add nothing to the Eq. 10
        # counters, and never extend the while_loop past the real rows
        row_ok = (jnp.arange(s) < n_valid)[:, None]
        f0 = jnp.where(row_ok, f0, 0)
        dist0 = jnp.where(f0 != 0, 0, jnp.full((s, n_pad), UNREACHED))
        # pad columns are born "visited" so no sweep form discovers them
        dist0 = jnp.where(row_ok & (jnp.arange(n_pad)[None, :] < n_real),
                          dist0, 0)

    forms = S.boolean_forms(adj, adj_pull, (row_src, row_dst), n_pad=n_pad,
                            s=s, bn=cfg.bn, bk=cfg.bk,
                            pull_chunk=cfg.pull_chunk,
                            use_kernel=use_kernel, interpret=interpret)

    if forced_dir is None:
        def choose(st: SweepState):
            stats = frontier_stats(st.frontier, st.dist, bs=bs, bn=cfg.bn,
                                   bk=cfg.bk)
            return choose_direction(stats, n_pad=n_pad, s=s, m_pad=m_pad,
                                    cfg=cfg)
    else:  # direction resolved at trace time: no stats, no switch
        choose = None

    fused = None
    if fused_steps:  # resolved upstream: kernel path, push pinned
        fused = S.fused_form("boolean", adj_pull, "push", bs=bs,
                             max_sweeps=fused_steps, interpret=interpret)

    st0 = S.make_state(f0, dist0, n_forms=3)
    return S.sweep_loop(forms, st0, max_steps=max_steps, deg=deg,
                        choose=choose,
                        forced_dir=0 if forced_dir is None else forced_dir,
                        fused=fused, fused_steps=fused_steps)


# --------------------------------------------------------------------------
# public drivers
# --------------------------------------------------------------------------

def apsp_engine_blocks(
        g: Union[CSRGraph, PreparedGraph],
        sources: Optional[Sequence[int]] = None, *,
        config: EngineConfig = EngineConfig(),
) -> Iterator[Tuple[np.ndarray, jax.Array, SweepState]]:
    """Stream (source_ids, dist_rows, raw_sweep_state) one source tile at a
    time — the non-materializing form for large n."""
    with obs.span("engine.plan") as plan_span:
        pg = g if isinstance(g, PreparedGraph) else prepare_graph(g)
        graph = pg.graph
        # TuningPlan overlay (no-op without one): tiles clamped to this
        # graph's padding, fused gate, cost constants
        config = autotune.apply(config, semiring="boolean", n_pad=pg.n_pad)
        n = graph.n_nodes
        srcs = np.arange(n, dtype=np.int32) if sources is None else \
            np.asarray(sources, np.int32)
        if srcs.size == 0:
            raise ValueError("apsp_engine: empty source list")
        if srcs.min() < 0 or srcs.max() >= n:
            raise ValueError(
                f"apsp_engine: sources must be in [0, {n}), got "
                f"[{srcs.min()}, {srcs.max()}]")
        use_kernel, interpret = config.kernel_path()
        max_steps = config.max_steps or n
        B = config.source_batch
        forced_dir = config.forced_form()
        # fused multi-sweep blocks only exist on the kernel push path; the
        # resolver returns None (-> per-sweep loop) whenever the capability
        # is missing or the whole-operand residency would blow the VMEM
        # budget
        fused_steps = 0
        if config.fused_steps and forced_dir in (None, PUSH):
            fused_steps = S.resolve_fused_steps(
                "boolean", "push", fused_steps=config.fused_steps,
                max_steps=max_steps, use_kernel=use_kernel, n_pad=pg.n_pad,
                bs=min(B, 128),
                budget=None if config.tuning is None
                else config.tuning.vmem_budget) or 0
            if fused_steps:
                forced_dir = PUSH   # fused blocks pin one direction
        # only materialize the O(n_pad^2) operands the resolved direction
        # can dispatch; the other slot gets a (1, 1) dummy its closure
        # never traces.  The kernel path runs *both* dense directions (and
        # the fused block) off the bit-packed pull operand; the dense int8
        # adjacency only feeds the XLA reference push.
        adj = pg.adj if (forced_dir in (None, PUSH) and not use_kernel) \
            else jnp.zeros((1, 1), jnp.int8)
        adj_pull = pg.adj_pull if (
            forced_dir in (None, PULL)
            or (forced_dir in (None, PUSH) and use_kernel)) else \
            jnp.zeros((1, 1), jnp.uint32)
        if forced_dir in (None, SPARSE):
            row_src, row_dst = pg.rows
            plan_span.set_metadata(
                sparse_layout="rows", rows=row_dst.shape[0],
                lane_fill=graph.n_edges / row_src.size)
        else:
            row_src, row_dst = jnp.zeros((1, 1), jnp.int32), \
                jnp.zeros((1,), jnp.int32)
            plan_span.set_metadata(sparse_layout="none")
        # from shapes alone: the (B, n_pad) int8 frontier table, the words
        # a vertex and the bytes of the table the sparse form gathers from
        # (packed past sweep.PACK_GATHER_BYTES), and the operands this
        # plan resolved
        plan_span.set_metadata(
            table_bytes=pg.n_pad * B,
            gather_words=S.gather_words((B, pg.n_pad)),
            gather_table_bytes=S.gather_table_bytes((B, pg.n_pad)),
            operand_bytes=sum(x.nbytes for x in (adj, adj_pull, row_src,
                                                 row_dst)))
    for lo in range(0, len(srcs), B):
        block = srcs[lo: lo + B]
        valid = len(block)
        with obs.span("engine.tile", valid=valid, tile=B):
            padded = np.zeros(B, np.int32)
            padded[:valid] = block
            st = _run_batch(adj, adj_pull, row_src, row_dst, pg.deg,
                            jnp.asarray(padded), jnp.int32(valid),
                            cfg=config, n_real=n, n_pad=pg.n_pad,
                            m_pad=graph.m_pad, max_steps=max_steps,
                            use_kernel=use_kernel, interpret=interpret,
                            forced_dir=forced_dir, fused_steps=fused_steps)
            dist = st.dist[:valid, :n]
        yield block, dist, st


def apsp_engine(g: Union[CSRGraph, PreparedGraph],
                sources: Optional[Sequence[int]] = None, *,
                config: EngineConfig = EngineConfig()) -> ApspResult:
    """Materialized batched APSP with per-sweep direction optimization.

    Returns distances for every requested source (default: all nodes),
    plus sweep/direction/work counters aggregated over source tiles.
    """
    rows, tallies = [], []
    for _, dist, st in apsp_engine_blocks(g, sources, config=config):
        rows.append(dist)
        tallies.append((st.step, st.dir_counts, st.edges_touched))
    with obs.span("engine.collect"):
        sweeps = jnp.int32(0)
        counts = jnp.zeros(3, jnp.int32)
        touched = jnp.float32(0.0)
        for step, dir_counts, edges_touched in tallies:
            sweeps = jnp.maximum(sweeps, step)
            counts = counts + dir_counts
            touched = touched + edges_touched
        return ApspResult(dist=jnp.concatenate(rows, axis=0), sweeps=sweeps,
                          direction_counts=counts, edges_touched=touched)
