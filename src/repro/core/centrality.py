"""Batched centrality analytics on the counting semiring — the "general
graph-analytics engine" framing of the paper's conclusion, grown past
distance reductions into exact betweenness.

Shortest-path *counting* is the same sweep as BFS under a different
algebra (Burkhardt's algebraic BFS): the loop state carries the pair
``(dist, sigma)`` and ⊕ adds path counts gated on dist-improvement ties
(:func:`repro.core.sweep.counting_forms`).  One batched counting run
feeds everything here:

  * **closeness / harmonic** — jit-reduced per source tile from the dist
    rows (integer sufficient statistics, finalized in float64 on host so
    results match the old per-block NumPy path exactly);
  * **eccentricity / radius / diameter** — exact per-source max distance
    over reachable targets (sampled bounds kept as
    :func:`eccentricity_sample`);
  * **betweenness** — exact Brandes: the forward counting sweeps produce
    ``(dist, sigma)`` per level (``dist`` IS the per-level frontier
    record: frontier at level t = ``dist == t``), and
    :func:`brandes_dependencies` runs the backward dependency
    accumulation level-by-level as one batched ``fori_loop`` over the
    recorded levels.

The forward engine (:func:`counting_apsp`) mirrors ``weighted_apsp``:
source tiles through the ONE sweep driver in ``core/sweep.py``, push
(f32 counting GEMM — the Pallas kernel on the kernel path) vs sparse
(scatter-add) chosen per sweep by the occupancy cost model under
``mode="auto"``, or pinned by ``mode``.  Large jobs route through the
sharded executor (``centrality(..., mesh=)``) — sources shard over the
mesh's data axes, sigma partials combine with the masked-add
⊕-reduction in ``core/distributed.py``.
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
from .engine import PreparedGraph, frontier_stats, prepare_graph
from .frontier import UNREACHED, one_hot_frontier
from .options import SweepOptions
from .sssp import multi_source

PUSH, SPARSE = 0, 1
COUNTING_FORM_NAMES = ("push", "sparse")

MEASURES = ("closeness", "harmonic", "eccentricity", "betweenness")


@dataclasses.dataclass(frozen=True)
class CentralityConfig(SweepOptions):
    """Static counting-engine parameters (a :class:`SweepOptions`
    subclass, hashable jit static arg) — the same shape as
    ``WeightedConfig`` with the pull form removed (bit-packing does not
    apply to f32 path counts).

    ``use_kernel=None`` resolves to "Pallas kernels iff on TPU" and
    ``mode="auto"`` to per-sweep switching, exactly like the
    boolean/tropical engines.
    """
    c_push: float = 1.0              # per f32 MAC in a live push tile
    c_sparse: float = 8.0            # per CSR gather + scatter-add lane

    _mode_names = COUNTING_FORM_NAMES  # push | sparse


class CountingResult(NamedTuple):
    dist: jax.Array              # (S, n) int32, -1 unreachable
    sigma: jax.Array             # (S, n) f32 shortest-path counts
    sweeps: jax.Array            # int32 — max sweeps over batches
    direction_counts: jax.Array  # (2,) int32 — push/sparse sweeps run


class CentralityResult(NamedTuple):
    """One batched analytics run.  Per-source arrays align with
    ``sources``; ``betweenness`` is over ALL nodes (the dependency sums
    contributed by the requested sources — exact betweenness when
    sources cover every node, a source-sampled estimate otherwise).
    ``radius``/``diameter`` are exact under the same condition.
    ``sigma_checksum`` is the sum of shortest-path counts over reachable
    pairs — a deterministic work fingerprint the benchmark regression
    gate pins (0.0 when betweenness was not requested)."""
    sources: np.ndarray
    closeness: Optional[np.ndarray]     # (S,) float64
    harmonic: Optional[np.ndarray]      # (S,) float64
    eccentricity: Optional[np.ndarray]  # (S,) int32
    betweenness: Optional[np.ndarray]   # (n,) float64
    radius: Optional[int]
    diameter: Optional[int]
    sweeps: int
    sigma_checksum: float


# --------------------------------------------------------------------------
# the batched counting engine (forward Brandes stage)
# --------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_real", "n_pad", "max_steps",
                                    "use_kernel", "interpret", "forced_dir",
                                    "fused_steps"))
def _run_counting_batch(adj, src_idx, dst_idx, deg, sources, n_valid, *,
                        cfg: CentralityConfig, n_real: int, n_pad: int,
                        max_steps: int, use_kernel: bool, interpret: bool,
                        forced_dir: Optional[int],
                        fused_steps: int = 0) -> S.SweepState:
    s = sources.shape[0]
    m_pad = src_idx.shape[0]
    bs = min(s, 128)

    f0 = one_hot_frontier(sources, n_pad, dtype=jnp.int8)
    row_ok = (jnp.arange(s) < n_valid)[:, None]
    f0 = jnp.where(row_ok, f0, 0)
    dist0 = jnp.where(f0 != 0, 0, jnp.full((s, n_pad), UNREACHED))
    # pad rows/cols are born "visited" with sigma 0: no sweep form ever
    # discovers them, so they stay inert in both halves of the state
    dist0 = jnp.where(row_ok & (jnp.arange(n_pad)[None, :] < n_real),
                      dist0, 0)
    sigma0 = jnp.where(f0 != 0, 1.0, 0.0).astype(jnp.float32)

    forms = S.counting_forms(adj, src_idx, dst_idx, n_pad=n_pad, s=s,
                             bn=cfg.bn, bk=cfg.bk, use_kernel=use_kernel,
                             interpret=interpret)

    if forced_dir is None:
        def choose(st: S.SweepState):
            stats = frontier_stats(st.frontier, st.dist[0], bs=bs,
                                   bn=128, bk=128)
            push_c = cfg.c_push * s * n_pad * n_pad * stats.live_tile_frac
            sparse_c = jnp.float32(cfg.c_sparse * s * m_pad)
            return (push_c > sparse_c).astype(jnp.int32)
    else:
        choose = None

    fused = None
    if fused_steps:  # resolved upstream: kernel path, push pinned
        fused = S.fused_form("counting", adj, "push", bs=bs,
                             max_sweeps=fused_steps, interpret=interpret)

    st0 = S.make_state(f0, (dist0, sigma0), n_forms=2)
    return S.sweep_loop(forms, st0, max_steps=max_steps, deg=deg,
                        choose=choose,
                        forced_dir=0 if forced_dir is None else forced_dir,
                        fused=fused, fused_steps=fused_steps)


def counting_apsp_blocks(g: Union[CSRGraph, PreparedGraph],
                         sources: Optional[Sequence[int]] = None, *,
                         config: CentralityConfig = CentralityConfig()):
    """Stream (source_ids, dist_rows, sigma_rows, raw_state) one source
    tile at a time through the counting engine."""
    pg = g if isinstance(g, PreparedGraph) else prepare_graph(g)
    config = autotune.apply(config, semiring="counting", n_pad=pg.n_pad)
    graph = pg.graph
    n = graph.n_nodes
    srcs = np.arange(n, dtype=np.int32) if sources is None else \
        np.asarray(sources, np.int32)
    if srcs.size == 0:
        raise ValueError("counting_apsp: empty source list")
    if srcs.min() < 0 or srcs.max() >= n:
        raise ValueError(
            f"counting_apsp: sources must be in [0, {n}), got "
            f"[{srcs.min()}, {srcs.max()}]")
    use_kernel, interpret = config.kernel_path()
    max_steps = config.max_steps or n
    B = config.source_batch
    forced = config.forced_form()
    fused_steps = 0
    if config.fused_steps and forced in (None, PUSH):
        fused_steps = S.resolve_fused_steps(
            "counting", "push", fused_steps=config.fused_steps,
            max_steps=max_steps, use_kernel=use_kernel, n_pad=pg.n_pad,
            bs=min(B, 128),
            budget=None if config.tuning is None
            else config.tuning.vmem_budget) or 0
        if fused_steps:
            forced = PUSH       # fused blocks pin the push form
    # the dense operand only materializes when push can dispatch
    adj = pg.adj if forced in (None, PUSH) else jnp.zeros((1, 1), jnp.int8)
    for lo in range(0, len(srcs), B):
        block = srcs[lo: lo + B]
        valid = len(block)
        padded = np.zeros(B, np.int32)
        padded[:valid] = block
        st = _run_counting_batch(adj, graph.src, graph.dst, pg.deg,
                                 jnp.asarray(padded), jnp.int32(valid),
                                 cfg=config, n_real=n, n_pad=pg.n_pad,
                                 max_steps=max_steps,
                                 use_kernel=use_kernel, interpret=interpret,
                                 forced_dir=forced, fused_steps=fused_steps)
        dist, sigma = st.dist
        yield block, dist[:valid, :n], sigma[:valid, :n], st


def counting_apsp(g: Union[CSRGraph, PreparedGraph],
                  sources: Optional[Sequence[int]] = None, *,
                  config: CentralityConfig = CentralityConfig()
                  ) -> CountingResult:
    """Materialized batched (dist, sigma) — BFS levels plus exact
    shortest-path counts for every requested source."""
    dist_rows, sig_rows = [], []
    sweeps = jnp.int32(0)
    counts = jnp.zeros(2, jnp.int32)
    for _, dist, sigma, st in counting_apsp_blocks(g, sources,
                                                   config=config):
        dist_rows.append(dist)
        sig_rows.append(sigma)
        sweeps = jnp.maximum(sweeps, st.step)
        counts = counts + st.dir_counts
    return CountingResult(dist=jnp.concatenate(dist_rows, axis=0),
                          sigma=jnp.concatenate(sig_rows, axis=0),
                          sweeps=sweeps, direction_counts=counts)


# --------------------------------------------------------------------------
# Brandes backward dependency accumulation
# --------------------------------------------------------------------------

@jax.jit
def _brandes_backward(src_idx: jax.Array, dst_idx: jax.Array,
                      dist: jax.Array, sigma: jax.Array,
                      max_level: jax.Array) -> jax.Array:
    """Batched Brandes dependencies δ (S, n) from (dist, sigma).

    ``dist`` is the per-level frontier record (frontier at level t ==
    ``dist == t``), so the backward pass walks levels deepest-first: for
    every edge (u, v) with ``dist[v] == dist[u] + 1 == t``,

        δ[u] += σ[u] / σ[v] · (1 + δ[v])

    accumulated as one frontier-masked scatter-add over the padded CSR
    lanes per level — the exact mirror of the forward sweeps' work
    shape.  δ[v] for a level-t node is final once all deeper levels have
    run, which the descending ``fori_loop`` guarantees."""
    s, n = dist.shape
    # sentinel column: padded lanes carry src = dst = n; level -2 never
    # matches a real level so their contributions are exactly zero
    d = jnp.concatenate(
        [dist, jnp.full((s, 1), -2, jnp.int32)], axis=1)
    sg = jnp.concatenate([sigma, jnp.ones((s, 1), jnp.float32)], axis=1)
    delta0 = jnp.zeros_like(sg)
    # loop-invariant lane gathers: levels and sigma never change during
    # the backward pass, only delta does
    du, dv = d[:, src_idx], d[:, dst_idx]
    sg_src = sg[:, src_idx]

    def body(i, delta):
        t = max_level - i
        on_level = (du == t - 1) & (dv == t)
        coeff = (1.0 + delta) / jnp.maximum(sg, 1.0)
        contrib = jnp.where(on_level, sg_src * coeff[:, dst_idx], 0.0)
        return delta.at[:, src_idx].add(contrib)

    delta = jax.lax.fori_loop(0, max_level, body, delta0)
    return delta[:, :n]


def brandes_dependencies(g: CSRGraph, dist: jax.Array, sigma: jax.Array
                         ) -> jax.Array:
    """Dependency accumulation δ[s, v] = Σ_{t reachable} σ_st(v)/σ_st for
    a block of sources, from the counting engine's (dist, sigma)."""
    max_level = jnp.maximum(jnp.max(dist), 0)
    return _brandes_backward(g.src, g.dst, jnp.asarray(dist),
                             jnp.asarray(sigma), max_level)


# --------------------------------------------------------------------------
# jit-batched per-tile reductions
# --------------------------------------------------------------------------

# column-chunked partial sums: one chunk's int32 distance total is
# bounded by CHUNK · diameter, so the int32 accumulator cannot wrap for
# any graph whose dense operand fits in memory (n ≲ 5·10^5 even in the
# path-graph worst case); the (S, n/CHUNK) partials finalize in
# int64/float64 on host
_REDUCE_CHUNK = 4096


@jax.jit
def _reduce_block(dist: jax.Array):
    """Per-source sufficient statistics from one (B, n) dist tile:
    reach count r-1 (int32 — counts fit trivially), column-chunked
    distance totals (int32 partials, exact) and harmonic partials (f32
    over ≤ 4096 terms each), eccentricity (int32).  Totals combine on
    host in int64/float64 — see :func:`centrality`."""
    s, n = dist.shape
    reach = dist > 0
    n_reach = reach.sum(axis=1).astype(jnp.int32)
    ecc = jnp.max(jnp.where(reach, dist, 0), axis=1,
                  initial=0).astype(jnp.int32)
    k = -(-n // _REDUCE_CHUNK)
    pad = k * _REDUCE_CHUNK - n
    dpad = jnp.pad(dist, ((0, 0), (0, pad)))     # pad dist 0 -> unreached
    dch = dpad.reshape(s, k, _REDUCE_CHUNK)
    rch = dch > 0
    tot_p = jnp.where(rch, dch, 0).sum(axis=2).astype(jnp.int32)
    har_p = jnp.where(rch, 1.0 / jnp.maximum(dch, 1), 0.0).sum(axis=2)
    return n_reach, tot_p, har_p, ecc


def _sigma_checksum_block(dist: jax.Array, sigma: jax.Array) -> float:
    """Sum of path counts over reachable pairs — the deterministic work
    fingerprint pinned by the benchmark regression gate."""
    return float(jnp.sum(jnp.where(dist >= 0, sigma, 0.0)))


# --------------------------------------------------------------------------
# the public analytics driver
# --------------------------------------------------------------------------

def centrality(g: Union[CSRGraph, PreparedGraph],
               sources: Optional[Sequence[int]] = None, *,
               measures: Sequence[str] = MEASURES,
               config: Optional[CentralityConfig] = None,
               mesh=None,
               method: str = "auto") -> CentralityResult:
    """One batched analytics run computing every requested measure.

    ``sources=None`` runs all nodes (exact betweenness / radius /
    diameter); a subset gives source-restricted sums (the standard
    source-sampled betweenness estimator, unscaled).  When betweenness
    is requested the forward pass runs the counting engine; otherwise
    the plain boolean engine serves the dist rows.  ``mesh=`` routes the
    forward runs through the semiring-generic sharded executor
    (``core/distributed.py``) — sources shard over the data axes and the
    non-idempotent counting ⊕ combines sigma partials with the
    masked-add reduction; the backward pass and reductions stay local.
    """
    measures = tuple(measures)
    unknown = set(measures) - set(MEASURES)
    if unknown:
        raise ValueError(f"unknown measures {sorted(unknown)}; "
                         f"available: {MEASURES}")
    pg = g if isinstance(g, PreparedGraph) else prepare_graph(g)
    graph = pg.graph
    n = graph.n_nodes
    srcs = np.arange(n, dtype=np.int32) if sources is None else \
        np.asarray(sources, np.int32)
    if srcs.size == 0:
        raise ValueError("centrality: empty source list")
    if srcs.min() < 0 or srcs.max() >= n:
        raise ValueError(
            f"centrality: sources must be in [0, {n}), got "
            f"[{srcs.min()}, {srcs.max()}]")
    config = config or CentralityConfig(
        source_batch=min(128, max(8, ((len(srcs) + 7) // 8) * 8)))
    need_sigma = "betweenness" in measures

    n_reach = np.zeros(len(srcs), np.int64)
    tot = np.zeros(len(srcs), np.int64)
    har = np.zeros(len(srcs), np.float64)
    ecc = np.zeros(len(srcs), np.int32)
    bc = np.zeros(n, np.float64) if need_sigma else None
    sweeps = 0
    checksum = 0.0

    def fold(lo, block, dist, sigma):
        nonlocal sweeps, checksum
        hi = lo + len(block)
        r_b, t_p, h_p, e_b = _reduce_block(dist)
        n_reach[lo:hi] = np.asarray(r_b)
        # chunked partials -> exact int64 / float64 totals on host
        tot[lo:hi] = np.asarray(t_p, np.int64).sum(axis=1)
        har[lo:hi] = np.asarray(h_p, np.float64).sum(axis=1)
        ecc[lo:hi] = np.asarray(e_b)
        if need_sigma:
            checksum += _sigma_checksum_block(dist, sigma)
            delta = np.asarray(brandes_dependencies(graph, dist, sigma),
                               np.float64)
            bc_local = delta.sum(axis=0)
            # Brandes never adds a source's own δ row at the source
            np.subtract.at(bc_local, block,
                           delta[np.arange(len(block)), block])
            bc[:] += bc_local

    if mesh is not None:
        from .distributed import ShardedConfig, sharded_apsp
        semiring = "counting" if need_sigma else "boolean"
        # honor the caller's form choice: the sharded executor names the
        # dense GEMM-analogue form "dense" where the counting engine
        # says "push"; "auto" keeps the per-sweep cost-model switch
        mode = {"push": "dense", "sparse": "sparse",
                "auto": "auto"}[config.mode]
        res = sharded_apsp(graph, srcs, mesh=mesh,
                           config=ShardedConfig(semiring=semiring,
                                                mode=mode,
                                                use_kernel=config.use_kernel,
                                                max_sweeps=config.max_steps,
                                                bn=config.bn,
                                                bk=config.bk))
        sweeps = int(res.sweeps)
        B = config.source_batch
        for lo in range(0, len(srcs), B):
            block = srcs[lo: lo + B]
            dist = res.dist[lo: lo + len(block)]
            sigma = res.sigma[lo: lo + len(block)] if need_sigma else None
            fold(lo, block, dist, sigma)
    elif need_sigma:
        lo = 0
        for block, dist, sigma, st in counting_apsp_blocks(
                pg, srcs, config=config):
            sweeps = max(sweeps, int(st.step))
            fold(lo, block, dist, sigma)
            lo += len(block)
    else:
        B = config.source_batch
        for lo in range(0, len(srcs), B):
            block = srcs[lo: lo + B]
            res = multi_source(pg, block, method=method, parents=False)
            sweeps = max(sweeps, int(res.eccentricity))
            fold(lo, block, res.dist, None)

    # finalize in float64 from the exact integer statistics —
    # Wasserman-Faust normalized closeness for disconnected graphs,
    # identical to the old per-block NumPy reduction
    frac = n_reach.astype(np.float64) / max(n - 1, 1)
    clo = np.where(tot > 0,
                   frac * n_reach / np.maximum(tot, 1).astype(np.float64),
                   0.0)

    reach_any = ecc > 0
    return CentralityResult(
        sources=srcs,
        closeness=clo if "closeness" in measures else None,
        harmonic=har if "harmonic" in measures else None,
        eccentricity=ecc if "eccentricity" in measures else None,
        betweenness=bc,
        radius=int(ecc[reach_any].min()) if ("eccentricity" in measures
                                             and reach_any.any()) else
        (0 if "eccentricity" in measures else None),
        diameter=int(ecc.max()) if "eccentricity" in measures else None,
        sweeps=sweeps,
        sigma_checksum=checksum,
    )


# --------------------------------------------------------------------------
# per-measure entry points (the quickstart API)
# --------------------------------------------------------------------------

def closeness(g: Union[CSRGraph, PreparedGraph],
              sources: Optional[np.ndarray] = None, *,
              block: int = 128, method: str = "auto") -> np.ndarray:
    """Closeness centrality C(u) = (r-1) / Σ_v d(u,v) over reachable v
    (Wasserman-Faust normalized for disconnected graphs), jit-batched."""
    cfg = CentralityConfig(source_batch=max(8, ((block + 7) // 8) * 8)
                           if block <= 128 else
                           ((block + 127) // 128) * 128)
    return centrality(g, sources, measures=("closeness",), config=cfg,
                      method=method).closeness


def harmonic(g: Union[CSRGraph, PreparedGraph],
             sources: Optional[np.ndarray] = None, *,
             block: int = 128, method: str = "auto") -> np.ndarray:
    """Harmonic centrality H(u) = Σ_{v≠u} 1/d(u,v), jit-batched."""
    cfg = CentralityConfig(source_batch=max(8, ((block + 7) // 8) * 8)
                           if block <= 128 else
                           ((block + 127) // 128) * 128)
    return centrality(g, sources, measures=("harmonic",), config=cfg,
                      method=method).harmonic


def betweenness(g: Union[CSRGraph, PreparedGraph],
                sources: Optional[np.ndarray] = None, *,
                normalized: bool = False,
                config: Optional[CentralityConfig] = None,
                mesh=None) -> np.ndarray:
    """Exact betweenness centrality (Brandes, directed, endpoints
    excluded) via the counting semiring.  ``sources`` restricts the
    dependency sums (source-sampled estimate); ``normalized=True``
    divides by (n-1)(n-2)."""
    res = centrality(g, sources, measures=("betweenness",), config=config,
                     mesh=mesh)
    bc = res.betweenness
    n = bc.shape[0]
    if normalized and n > 2:
        bc = bc / float((n - 1) * (n - 2))
    return bc


def eccentricity(g: Union[CSRGraph, PreparedGraph],
                 sources: Optional[np.ndarray] = None, *,
                 config: Optional[CentralityConfig] = None,
                 mesh=None) -> dict:
    """Exact eccentricities (over reachable targets) plus radius /
    diameter — exact when ``sources`` covers every node (the default)."""
    res = centrality(g, sources, measures=("eccentricity",), config=config,
                     mesh=mesh)
    return {"ecc": res.eccentricity, "radius": res.radius,
            "diameter": res.diameter}


def eccentricity_sample(g: CSRGraph, n_samples: int = 64, *,
                        seed: int = 0, method: str = "auto"):
    """Sampled eccentricities → (radius_upper, diameter_lower) estimates
    (Takes-Kosters-style bounds from a random source set — the paper's
    ε(i) ≈ log n observation is checkable with this).  For exact values
    use :func:`eccentricity`."""
    rng = np.random.default_rng(seed)
    sources = rng.integers(0, g.n_nodes, n_samples)
    res = centrality(g, sources, measures=("eccentricity",), method=method)
    ecc_arr = res.eccentricity
    return {"radius_upper": int(ecc_arr[ecc_arr > 0].min())
            if (ecc_arr > 0).any() else 0,
            "diameter_lower": int(ecc_arr.max()),
            "ecc_mean": float(ecc_arr.mean())}
