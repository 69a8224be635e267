"""Weighted-graph DAWN — the paper's §5 future-work direction, grown into
a first-class tropical-semiring engine.

The paper closes with "addressing the balance between optimizing matrix
operations and managing the consumption of (min,+) operations … to expand
the applicability of DAWN on weighted graphs".  With the semiring sweep
layer (core/sweep.py) that balance is literal: the same
direction-optimizing batch driver that picks boolean sweep forms now
picks between the tropical forms —

  DENSE  — f32 min-plus GEMM-analogue of the boolean push sweep
           (``cand[s, j] = min_k dist[s, k] + W[k, j]`` over frontier
           rows; cost proportional to the live tile fraction); on the
           kernel path this is the fused Pallas min-plus sweep with
           settled-bound tile skipping (kernels/tropical);
  SPARSE — edge-parallel scatter-min relaxation over CSR lanes (cost
           O(S · m_pad) regardless of occupancy); kernel path: the
           Pallas edge-block relax

— chosen per sweep by the occupancy cost model under ``mode="auto"``, or
pinned by ``mode``, exactly mirroring core/engine.py.  Public entry
points:

  * ``minplus_sssp``   — single-source (min,+) sweeps through the shared
                         driver (frontier-gated Bellman-Ford; sweep count
                         ≤ hop count of the longest shortest path, work
                         O(hops·m) — the direct generalization of BOVM's
                         O(ε·m));
  * ``weighted_apsp``  — batched multi-source tropical APSP with the
                         direction optimizer;
  * ``bucketed_sssp``  — small integer weights via unit-hop expansion
                         through the UNWEIGHTED sweep machinery (the
                         matrix-op/(min,+) trade the paper anticipates,
                         made explicit).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import CSRGraph
from . import autotune
from . import sweep as S
from .engine import frontier_stats
from .frontier import one_hot_frontier
from .options import SweepOptions
from .sovm import sovm_sssp

INF = jnp.float32(jnp.inf)

DENSE, SPARSE = 0, 1
WEIGHTED_FORM_NAMES = ("dense", "sparse")


class WeightedResult(NamedTuple):
    dist: jax.Array          # (n,) float32; inf = unreachable
    sweeps: jax.Array


class WeightedApspResult(NamedTuple):
    dist: jax.Array              # (S, n) float32; inf = unreachable
    sweeps: jax.Array            # int32 — max sweeps over batches
    direction_counts: jax.Array  # (2,) int32 — dense/sparse sweeps run
    edges_touched: jax.Array     # float32 — relaxed-edge work counter


@dataclasses.dataclass(frozen=True)
class WeightedConfig(SweepOptions):
    """Static tropical-engine parameters (a :class:`SweepOptions`
    subclass, hashable jit static arg).

    Cost-model units: ``c_dense`` per f32 add+min lane in a live dense
    tile, ``c_sparse`` per CSR relax lane — same shape as the boolean
    engine's model with the pull form removed (bit-packing does not apply
    to f32 distances).

    ``use_kernel=None`` resolves to "Pallas kernels iff on TPU", exactly
    like ``EngineConfig``; the kernel closures come from the semiring
    kernel registry via ``sweep.tropical_forms``.  ``mode="auto"``
    mirrors the boolean engine too: per-sweep occupancy switching.

    ``max_sweeps`` is this engine's historical spelling of the base
    ``max_steps`` hop bound; setting either sets both.
    """
    source_batch: int = 64           # sources per tile (multiple of 8)
    max_sweeps: Optional[int] = None  # alias of max_steps (hop bound)
    chunk: int = 128                 # dense min-plus dst cols per map step
    eb: int = 128                    # sparse relax kernel edges per step
    c_dense: float = 1.0
    c_sparse: float = 8.0

    _mode_names = WEIGHTED_FORM_NAMES  # dense | sparse

    def __post_init__(self):
        # fold the two spellings of the hop bound into one value
        bound = self.max_sweeps if self.max_sweeps is not None \
            else self.max_steps
        object.__setattr__(self, "max_sweeps", bound)
        object.__setattr__(self, "max_steps", bound)
        super().__post_init__()


@dataclasses.dataclass
class PreparedWeightedGraph:
    """Device-resident tropical operands (dense O(n_pad^2) form lazy)."""
    graph: CSRGraph
    w_edges: jax.Array    # (m_pad,) float32; +inf on padded lanes
    deg: jax.Array        # (n_pad,) float32 out-degrees (0 on pad)
    n_pad: int
    # content epoch of the source graph at prepare time (0 = static)
    epoch: int = 0
    _wdense: Optional[jax.Array] = dataclasses.field(default=None,
                                                     repr=False)

    @property
    def wdense(self) -> jax.Array:
        """(n_pad, n_pad) f32 weight matrix, +inf non-edges (dense
        operand); parallel edges resolve to the min weight."""
        if self._wdense is None:
            g = self.graph
            self._wdense = jnp.full((self.n_pad, self.n_pad), INF).at[
                g.src, g.dst].min(self.w_edges)
        return self._wdense


def prepare_weighted(g, weights=None, *,
                     align: int = 128) -> PreparedWeightedGraph:
    """Normalize weights to the padded edge lanes and build the O(n)
    operands; the dense weight matrix materializes lazily.

    Accepts a plain :class:`CSRGraph` (``weights`` required) or a
    weighted :class:`repro.graph.dynamic.DynamicCSRGraph` (lane weights
    come from its merged view; the content ``epoch`` is recorded for
    downstream staleness checks)."""
    epoch = 0
    if hasattr(g, "view"):            # DynamicCSRGraph duck-type
        epoch = int(g.epoch)
        if weights is None:
            weights = g.view_weights()
        g = g.view()
    assert weights is not None, "prepare_weighted needs edge weights"
    w = np.asarray(weights, np.float32)
    assert w.ndim == 1 and w.size >= g.n_edges, \
        f"need >= {g.n_edges} weights, got shape {w.shape}"
    assert (w[: g.n_edges] >= 0).all(), "weights must be non-negative"
    lanes = np.full(g.m_pad, np.inf, np.float32)
    lanes[: g.n_edges] = w[: g.n_edges]
    n_pad = g.n_padded(align)
    deg = jnp.zeros(n_pad, jnp.float32).at[: g.n_nodes].set(
        g.out_degrees().astype(jnp.float32))
    return PreparedWeightedGraph(graph=g, w_edges=jnp.asarray(lanes),
                                 deg=deg, n_pad=n_pad, epoch=epoch)


# --------------------------------------------------------------------------
# single-source (min,+) sweeps
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_sweeps",))
def minplus_sssp(g: CSRGraph, weights: jax.Array, source, *,
                 max_sweeps: Optional[int] = None) -> WeightedResult:
    """(min,+) sweep SSSP through the shared driver.  weights (m_pad,)
    float32 ≥ 0 (padded entries ignored via the +inf mask)."""
    n = g.n_nodes
    max_sweeps = n if max_sweeps is None else max_sweeps
    src_id = jnp.asarray(source, jnp.int32)
    dist0 = jnp.full(n + 1, INF).at[src_id].set(0.0)
    f0 = jnp.zeros(n + 1, jnp.int8).at[src_id].set(1)
    w = jnp.where(g.src < n, weights, INF)

    _, sparse = S.tropical_forms(None, g.src, g.dst, w)
    st = S.sweep_loop((sparse,), S.make_state(f0, dist0, n_forms=1),
                      max_steps=max_sweeps)
    return WeightedResult(st.dist[:n], st.sweeps)


# --------------------------------------------------------------------------
# batched direction-optimizing tropical APSP
# --------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_real", "n_pad", "max_sweeps",
                                    "use_kernel", "interpret", "forced_dir",
                                    "fused_steps"))
def _run_weighted_batch(wdense, src_idx, dst_idx, w_edges, deg, sources,
                        n_valid, *, cfg: WeightedConfig, n_real: int,
                        n_pad: int, max_sweeps: int, use_kernel: bool,
                        interpret: bool,
                        forced_dir: Optional[int],
                        fused_steps: int = 0) -> S.SweepState:
    s = sources.shape[0]
    m_pad = src_idx.shape[0]
    bs = min(s, 128)

    f0 = one_hot_frontier(sources, n_pad, dtype=jnp.int8)
    row_ok = (jnp.arange(s) < n_valid)[:, None]
    f0 = jnp.where(row_ok, f0, 0)
    # pad rows/cols stay +inf with empty frontiers: no candidate ever
    # improves them, so they are inert without masks
    dist0 = jnp.where(f0 != 0, 0.0, jnp.full((s, n_pad), INF))

    forms = S.tropical_forms(wdense, src_idx, dst_idx, w_edges,
                             n_pad=n_pad, chunk=cfg.chunk,
                             use_kernel=use_kernel, interpret=interpret,
                             bn=cfg.bn, bk=cfg.bk, eb=cfg.eb)
    if forms[0] is None:
        forms = (forms[1], forms[1])  # sparse pinned; keep switch arity 2

    if forced_dir is None:
        def choose(st: S.SweepState):
            stats = frontier_stats(st.frontier, st.dist, bs=bs, bn=128,
                                   bk=128, unreached=jnp.isinf(st.dist))
            dense_c = cfg.c_dense * s * n_pad * n_pad * stats.live_tile_frac
            sparse_c = jnp.float32(cfg.c_sparse * s * m_pad)
            return (dense_c > sparse_c).astype(jnp.int32)
    else:
        choose = None

    fused = None
    if fused_steps:  # resolved upstream: kernel path, dense pinned
        fused = S.fused_form("tropical", wdense, "dense", bs=bs,
                             max_sweeps=fused_steps, interpret=interpret)

    st0 = S.make_state(f0, dist0, n_forms=2)
    return S.sweep_loop(forms, st0, max_steps=max_sweeps, deg=deg,
                        choose=choose,
                        forced_dir=0 if forced_dir is None else forced_dir,
                        fused=fused, fused_steps=fused_steps)


def weighted_apsp(g: Union[CSRGraph, PreparedWeightedGraph],
                  weights=None,
                  sources: Optional[Sequence[int]] = None, *,
                  config: WeightedConfig = WeightedConfig()
                  ) -> WeightedApspResult:
    """Batched multi-source tropical APSP with direction optimization.

    Pass a :class:`PreparedWeightedGraph` (weights=None) to reuse
    operands across calls (the serving path does).  Distances are
    float32 with +inf for unreachable targets.
    """
    pw = g if isinstance(g, PreparedWeightedGraph) else \
        prepare_weighted(g, weights)
    config = autotune.apply(config, semiring="tropical", n_pad=pw.n_pad)
    graph = pw.graph
    n = graph.n_nodes
    srcs = np.arange(n, dtype=np.int32) if sources is None else \
        np.asarray(sources, np.int32)
    if srcs.size == 0:
        raise ValueError("weighted_apsp: empty source list")
    if srcs.min() < 0 or srcs.max() >= n:
        raise ValueError(
            f"weighted_apsp: sources must be in [0, {n}), got "
            f"[{srcs.min()}, {srcs.max()}]")
    max_sweeps = config.max_sweeps or n
    B = config.source_batch
    use_kernel, interpret = config.kernel_path()
    forced = config.forced_form()
    fused_steps = 0
    if config.fused_steps and forced in (None, DENSE):
        fused_steps = S.resolve_fused_steps(
            "tropical", "dense", fused_steps=config.fused_steps,
            max_steps=max_sweeps, use_kernel=use_kernel, n_pad=pw.n_pad,
            bs=min(B, 128),
            budget=None if config.tuning is None
            else config.tuning.vmem_budget) or 0
        if fused_steps:
            forced = DENSE      # fused blocks pin the dense form
    # only materialize the O(n_pad^2) dense operand when it can dispatch
    wdense = pw.wdense if forced in (None, DENSE) else None

    rows = []
    sweeps = jnp.int32(0)
    counts = jnp.zeros(2, jnp.int32)
    touched = jnp.float32(0.0)
    for lo in range(0, len(srcs), B):
        block = srcs[lo: lo + B]
        valid = len(block)
        padded = np.zeros(B, np.int32)
        padded[:valid] = block
        st = _run_weighted_batch(wdense, graph.src, graph.dst, pw.w_edges,
                                 pw.deg, jnp.asarray(padded),
                                 jnp.int32(valid), cfg=config, n_real=n,
                                 n_pad=pw.n_pad, max_sweeps=max_sweeps,
                                 use_kernel=use_kernel, interpret=interpret,
                                 forced_dir=forced, fused_steps=fused_steps)
        rows.append(st.dist[:valid, :n])
        sweeps = jnp.maximum(sweeps, st.step)
        counts = counts + st.dir_counts
        touched = touched + st.edges_touched
    return WeightedApspResult(dist=jnp.concatenate(rows, axis=0),
                              sweeps=sweeps, direction_counts=counts,
                              edges_touched=touched)


# --------------------------------------------------------------------------
# small-integer weights through the unweighted machinery
# --------------------------------------------------------------------------

def expand_integer_weights(g: CSRGraph, weights: np.ndarray) -> CSRGraph:
    """Unit-hop expansion: a weight-w edge (u→v) becomes a path
    u → x₁ → … → x_{w-1} → v of unit edges (host-side construction)."""
    src, dst = g.edge_arrays_np()
    weights = np.asarray(weights[: g.n_edges], dtype=np.int64)
    assert (weights >= 1).all(), "integer weights must be ≥ 1"
    n = g.n_nodes
    new_src, new_dst = [], []
    next_virtual = n
    for u, v, w in zip(src, dst, weights):
        if w == 1:
            new_src.append(u); new_dst.append(v)
            continue
        chain = [u] + list(range(next_virtual, next_virtual + w - 1)) + [v]
        next_virtual += w - 1
        for a, b in zip(chain[:-1], chain[1:]):
            new_src.append(a); new_dst.append(b)
    return CSRGraph.from_edges(np.asarray(new_src), np.asarray(new_dst),
                               next_virtual, dedup=False)


def bucketed_sssp(g: CSRGraph, weights: np.ndarray, source: int
                  ) -> WeightedResult:
    """Small-integer-weight SSSP through the unweighted SOVM machinery."""
    eg = expand_integer_weights(g, weights)
    st = sovm_sssp(eg, source)
    dist = jnp.where(st.dist[: g.n_nodes] < 0, INF,
                     st.dist[: g.n_nodes].astype(jnp.float32))
    return WeightedResult(dist, st.sweeps)


def dijkstra_oracle(g: CSRGraph, weights: np.ndarray,
                    source: int) -> np.ndarray:
    """scipy Dijkstra reference for tests."""
    import scipy.sparse as sp
    import scipy.sparse.csgraph as csgraph
    src, dst = g.edge_arrays_np()
    mat = sp.csr_matrix((np.asarray(weights[: g.n_edges], np.float64),
                         (src, dst)), shape=(g.n_nodes, g.n_nodes))
    return csgraph.dijkstra(mat, indices=source, directed=True)
