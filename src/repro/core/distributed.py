"""Semiring-generic sharded sweep executor — DAWN's multi-device path.

The paper's APSP regime O(S_wcc · E_wcc) is embarrassingly parallel over
sources, and the algebraic formulation (Burkhardt 2019's algebraic BFS;
the paper's Eq. 9 union-as-matrix-op) makes the per-sweep relaxation
itself shardable over vertices.  This module scales BOTH axes, for any
semiring the sweep layer knows:

  * **sources** shard over the mesh's data-parallel axes (every axis not
    named ``model``): each shard runs the unified driver
    (:func:`repro.core.sweep.sweep_loop`) on its ``(S/D, n_pad)`` state
    rows with zero per-sweep communication; only the Fact-1 convergence
    predicate is psum'd across the whole mesh so every shard executes the
    same trip count.
  * **vertices** (optional, mesh axis ``model``) shard the sweep operand:
    the dense adjacency / weight matrix splits into K-row blocks (the
    contraction dim), the CSR lanes into per-shard dst-block partitions
    (:func:`repro.graph.partition.edge_partition_global`; the boolean
    sparse form reads each partition as destination rows,
    :func:`repro.core.sweep.dst_rows`).  Each sweep
    computes a *partial* candidate set from its local block and
    cross-shard combines with the semiring's ⊕ — OR (``lax.pmax``) for
    boolean, min (``lax.pmin``) for tropical, masked ADD (``lax.psum``
    of gated partial path counts) for the counting semiring — before
    the epilogue.  The idempotent ⊕'s (OR, min) may fold epilogue
    outputs; the non-idempotent counting ⊕ must sum *gated partials*
    instead so every shortest path is counted exactly once.  All are
    associative, commutative and exact (f32 min does not round; f32
    adds of path counts are exact under 2^24), so sharded distances
    and sweep counts are **bit-identical** to the single-device engines.

Forms dispatch through :mod:`repro.kernels.registry` exactly as the
single-device engines do (``use_kernel`` / ``interpret`` resolve the same
way; the rectangular Pallas push / min-plus kernels take the K-row
blocks directly), and this module carries no loop of its own — the ONE
``lax.while_loop`` stays in ``core/sweep.py``; the old boolean-only
msbfs builder and its private loop plumbing are gone.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph.csr import CSRGraph, _round_up
from ..graph.partition import edge_partition_global
from ..kernels import registry as kernel_registry
from . import autotune
from . import sweep as S
from .engine import _resolve_kernel, frontier_stats
from .frontier import (UNREACHED, one_hot_frontier, pack_bits,
                       unpack_bits)
from .options import SweepOptions

INF = jnp.float32(jnp.inf)

MODEL_AXIS = "model"

DENSE, SPARSE = 0, 1
SHARDED_FORM_NAMES = ("dense", "sparse")


@dataclasses.dataclass(frozen=True)
class ShardedConfig(SweepOptions):
    """Static sharded-executor parameters (a :class:`SweepOptions`
    subclass, hashable jit static arg).

    ``semiring`` picks the algebra ("boolean" unweighted BFS, "tropical"
    (min,+) APSP — weights required, "counting" shortest-path counting
    with (dist, sigma) state for the centrality subsystem).  ``mode``
    pins the sweep form —
    dense (the GEMM-analogue push; the collective-friendly matrix form)
    or sparse (edge-partitioned scatter) — or lets ``auto`` switch per
    sweep on the same occupancy cost model the single-device engines use
    (stats pmean'd over the data axes so every shard picks the same
    branch).  ``use_kernel=None`` resolves to "Pallas kernels iff on
    TPU", exactly like ``EngineConfig``/``WeightedConfig``.
    """
    mode: str = "dense"                # dense | sparse | auto
    semiring: str = "boolean"          # boolean | tropical | counting
    max_sweeps: Optional[int] = None   # alias of max_steps (hop bound)
    # kernel / reference tiling knobs (mirror the single-device configs)
    eb: int = 128
    chunk: int = 128
    # auto-mode cost constants (same units as the single-device engines)
    c_dense: float = 1.0
    c_sparse: float = 8.0
    # fused multi-sweep blocks (boolean, mode="dense", kernel path,
    # C == 1 only): 0 = off, K > 0 = K sweeps per launch, -1 = whole
    # fixpoint.  Vertex sharding (C > 1) needs a cross-shard ⊕ between
    # sweeps, so it always falls back to the per-sweep loop; with C == 1
    # only the Fact-1 predicate crosses shards and the fused block's
    # (prod, stopped) scalars psum/pmax-combine instead (fused_combine).

    _mode_names = SHARDED_FORM_NAMES   # dense | sparse

    def __post_init__(self):
        assert self.semiring in ("boolean", "tropical", "counting"), \
            self.semiring
        bound = self.max_sweeps if self.max_sweeps is not None \
            else self.max_steps
        object.__setattr__(self, "max_sweeps", bound)
        object.__setattr__(self, "max_steps", bound)
        super().__post_init__()

    @property
    def tropical(self) -> bool:
        return self.semiring == "tropical"

    @property
    def counting(self) -> bool:
        return self.semiring == "counting"

    @property
    def need_dense(self) -> bool:
        return self.mode in ("dense", "auto")

    @property
    def need_sparse(self) -> bool:
        return self.mode in ("sparse", "auto")


class ShardedApspResult(NamedTuple):
    dist: jax.Array              # (S, n) int32 boolean / float32 tropical
    sweeps: jax.Array            # scalar int32 — matches the 1-device count
    direction_counts: jax.Array  # (2,) int32 — dense/sparse sweeps run
    # (S, n) f32 shortest-path counts — counting semiring only, else None
    sigma: Optional[jax.Array] = None
    # f32 Eq. 10 useful-work counter, psum'd over the data shards (the
    # per-shard partials are exact integer sums, so the total is
    # independent of the mesh shape); 0 on the fused-kernel path, which
    # never materializes per-sweep frontiers to weigh against ``deg``
    edges_touched: Optional[jax.Array] = None


@dataclasses.dataclass
class ShardedOperands:
    """Device-resident sharded operands, built once per (graph, mesh,
    config) and reused across calls (the serving path caches one)."""
    graph: CSRGraph
    mesh: Mesh
    config: ShardedConfig
    n_pad: int
    n_shards: int            # model-axis extent C (1 = no vertex sharding)
    m_local: int             # padded CSR lanes per shard (cost model)
    dense_op: jax.Array      # (n_pad, n_pad) adj int8 / weights f32,
    #                          K-row-sharded over model; (1, 1) dummy
    src_l: jax.Array         # (C, e_pad) sharded / (m_pad,) replicated
    dst_l: jax.Array         #   global ids, CSR sentinel n; (1,) dummies
    #                          on the boolean semiring, which reads rows
    row_src: jax.Array       # boolean sparse operand (sweep.dst_rows):
    row_dst: jax.Array       #   (C, W, R) / (C, R) sharded, one layout per
    #                          lane partition, or (W, R) / (R,)
    #                          replicated; (1, 1) / (1,) dummies
    w_l: jax.Array           # tropical lane weights (+inf pad); (1,) dummy
    w_min: jax.Array         # scalar f32 min finite edge weight (0 dummy)
    deg: jax.Array           # (n_pad,) f32 out-degrees, replicated (0 pad)


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def dp_extent(mesh: Mesh) -> int:
    out = 1
    for a in _dp_axes(mesh):
        out *= mesh.shape[a]
    return out


def prepare_sharded(g: CSRGraph, mesh: Mesh, *, weights=None,
                    config: ShardedConfig = ShardedConfig(),
                    dense_op: Optional[jax.Array] = None
                    ) -> ShardedOperands:
    """Pad, partition and device_put the operands ``config`` can
    dispatch.  ``n_pad`` rounds to a multiple of 128·C so the K-row
    blocks stay MXU-tileable; arbitrary (non-divisible) n and source
    counts are handled by padding, exactly like the single-device
    engines.  Pass ``dense_op`` (an already-materialized (n_pad, n_pad)
    adjacency / weight matrix, e.g. ``PreparedGraph.adj`` /
    ``PreparedWeightedGraph.wdense``) to avoid holding a second dense
    copy when the padded size matches — the serving path does this on
    meshes without vertex sharding."""
    C = dict(mesh.shape).get(MODEL_AXIS, 1)
    n_pad = g.n_padded(128 * C)
    # TuningPlan overlay happens here, where the config is baked into the
    # prepared operands (sharded_apsp refuses config= on a ShardedOperands)
    config = autotune.apply(config, semiring=config.semiring, n_pad=n_pad)
    tropical = config.tropical

    lanes = None
    w_min = jnp.float32(0.0)
    if tropical:
        if weights is None:
            raise ValueError("tropical sharding needs edge weights")
        w = np.asarray(weights, np.float32)
        assert w.ndim == 1 and w.size >= g.n_edges, \
            f"need >= {g.n_edges} weights, got shape {w.shape}"
        assert (w[: g.n_edges] >= 0).all(), "weights must be non-negative"
        lanes = np.full(g.m_pad, np.inf, np.float32)
        lanes[: g.n_edges] = w[: g.n_edges]
        w_min = jnp.float32(lanes[: g.n_edges].min() if g.n_edges
                            else np.inf)

    if not config.need_dense:
        if dense_op is not None:
            raise ValueError(
                "prepare_sharded: dense_op= passed but config.mode="
                f"{config.mode!r} never dispatches the dense form — it "
                "would be silently dropped")
        dense_op = jnp.zeros((1, 1), jnp.float32 if tropical else jnp.int8)
    else:
        if dense_op is None:
            if tropical:
                dense_op = jnp.full((n_pad, n_pad), INF).at[
                    g.src, g.dst].min(jnp.asarray(lanes))
            else:
                dense_op = g.to_dense_padded(n_pad, dtype=jnp.int8)
        else:
            assert dense_op.shape == (n_pad, n_pad), \
                (dense_op.shape, n_pad)
        spec = P(MODEL_AXIS, None) if C > 1 else P()
        dense_op = jax.device_put(dense_op, NamedSharding(mesh, spec))

    src_l = dst_l = jnp.zeros((1,), jnp.int32)
    w_l = jnp.zeros((1,), jnp.float32)
    row_src, row_dst = jnp.zeros((1, 1), jnp.int32), \
        jnp.zeros((1,), jnp.int32)
    boolean = config.semiring == "boolean"
    m_local = g.m_pad
    if config.need_sparse:
        if C > 1:
            parts = edge_partition_global(g, C, weights=lanes)
            m_local = parts["e_pad"]
            if boolean:
                rows = [_partition_rows(src, dst, g.n_nodes) for src, dst
                        in zip(np.asarray(parts["src"]),
                               np.asarray(parts["dst"]))]
                row_src = jax.device_put(
                    jnp.stack([r for r, _ in rows]),
                    NamedSharding(mesh, P(MODEL_AXIS, None, None)))
                row_dst = jax.device_put(
                    jnp.stack([r for _, r in rows]),
                    NamedSharding(mesh, P(MODEL_AXIS, None)))
            else:
                lane_sharding = NamedSharding(mesh, P(MODEL_AXIS, None))
                src_l = jax.device_put(parts["src"], lane_sharding)
                dst_l = jax.device_put(parts["dst"], lane_sharding)
                if tropical:
                    w_l = jax.device_put(parts["w"], lane_sharding)
        else:
            # replicated on every device of the mesh, not left on the
            # default device for each call to copy out
            replicated = NamedSharding(mesh, P())
            if boolean:
                row_src, row_dst = jax.device_put(
                    S.dst_rows(g.indptr_t, g.indices_t, n_real=g.n_nodes),
                    replicated)
            else:
                src_l = jax.device_put(g.src, replicated)
                dst_l = jax.device_put(g.dst, replicated)
                if tropical:
                    w_l = jax.device_put(lanes, replicated)

    deg = jnp.zeros(n_pad, jnp.float32).at[: g.n_nodes].set(
        jnp.asarray(g.out_degrees(), jnp.float32))
    deg = jax.device_put(deg, NamedSharding(mesh, P()))

    return ShardedOperands(graph=g, mesh=mesh, config=config, n_pad=n_pad,
                           n_shards=C, m_local=m_local, dense_op=dense_op,
                           src_l=src_l, dst_l=dst_l, w_l=w_l, w_min=w_min,
                           row_src=row_src, row_dst=row_dst, deg=deg)


def _partition_rows(src: np.ndarray, dst: np.ndarray, n: int):
    """Destination rows of one lane partition (global ids, sentinel
    ``n``), padded like every partition to the same row count."""
    real = dst < n
    part = CSRGraph.from_edges(src[real], dst[real], n, dedup=False,
                               remove_self_loops=False, pad_to=len(src))
    return S.dst_rows(part.indptr_t, part.indices_t, n_real=n)


# --------------------------------------------------------------------------
# the shard_map'd runner (built once per mesh/config/shape, lru-cached)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _make_runner(mesh: Mesh, cfg: ShardedConfig, n_pad: int, n_real: int,
                 m_local: int, use_kernel: bool, interpret: bool,
                 C: int):
    dp = _dp_axes(mesh)
    tropical = cfg.tropical
    counting = cfg.counting
    vertex_sharded = C > 1
    nk = n_pad // C
    all_axes = tuple(mesh.axis_names)

    def run_local(dense_l, src_e, dst_e, w_e, row_src, row_dst, w_min,
                  deg_l, f0_l, dist0_l, sigma0_l, steps):
        if src_e.ndim == 2:              # (1, e_pad) model-axis block row
            src_e, dst_e = src_e[0], dst_e[0]
            w_e = w_e[0] if w_e.ndim == 2 else w_e
        if row_src.ndim == 3:            # (1, W, R) model-axis block
            row_src, row_dst = row_src[0], row_dst[0]
        s_l = f0_l.shape[0]
        fused = fused_combine = None
        fused_steps_l = 0

        def or_combine(new_p):
            """Cross-shard ⊕ = OR, bit-packed: all-gather uint32 words
            (S_l·n_pad/8 bytes/shard — 8x under an int8 pmax; OR of words
            is exactly the union of bits) and fold them locally."""
            packed = pack_bits(new_p != 0)                     # (S_l, W)
            gathered = jax.lax.all_gather(packed, MODEL_AXIS)  # (C, ...)
            words = functools.reduce(jnp.bitwise_or,
                                     [gathered[i] for i in range(C)])
            return unpack_bits(words, n_pad).astype(jnp.int8)

        def counting_epilogue(cand_p, d, sg, step):
            """⊕ = masked ADD, the non-idempotent cross-shard combine:
            each shard's candidate counts are gated to zero where they
            cannot contribute, then SUMMED (psum) so every shortest path
            is counted exactly once — folding epilogue *outputs* (the
            OR/min trick) would double-gate the counts."""
            if vertex_sharded:
                cand = jax.lax.psum(cand_p, MODEL_AXIS)
            else:
                cand = cand_p
            new = (cand > 0) & (d == UNREACHED)
            return (new.astype(jnp.int8),
                    (jnp.where(new, step, d), jnp.where(new, cand, sg)))

        # ---- dense form: the GEMM-analogue push over the local K block
        dense_form = None
        if cfg.need_dense:
            if counting:
                if use_kernel:
                    Kc = kernel_registry.get("counting").forms
                    bsc = min(s_l, 128)

                    def partial_cand(fs_k, d, sg, step):
                        # reconstruct the gated partial from the kernel's
                        # epilogue outputs: where new_p, nsg_p IS cand_p,
                        # and dropped zeros don't change the psum
                        new_p, _, nsg_p = Kc["push"](
                            fs_k, dense_l, d, sg, step, bs=bsc, bn=cfg.bn,
                            bk=cfg.bk, interpret=interpret)
                        return jnp.where(new_p != 0, nsg_p, 0.0)
                else:
                    def partial_cand(fs_k, d, sg, step):
                        cand = jax.lax.dot_general(
                            fs_k, dense_l.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
                        return jnp.where(d == UNREACHED, cand, 0.0)

                if vertex_sharded:
                    def dense_form(f, ds, p, step):
                        d, sg = ds
                        k0 = jax.lax.axis_index(MODEL_AXIS) * nk
                        f_k = jax.lax.dynamic_slice_in_dim(f, k0, nk, 1)
                        sg_k = jax.lax.dynamic_slice_in_dim(sg, k0, nk, 1)
                        fs_k = jnp.where(f_k != 0, sg_k, 0.0)
                        cand_p = partial_cand(fs_k, d, sg, step)
                        new, ds2 = counting_epilogue(cand_p, d, sg, step)
                        return new, ds2, p
                else:
                    dense_form = S.counting_forms(
                        dense_l, jnp.zeros((1,), jnp.int32),
                        jnp.zeros((1,), jnp.int32), n_pad=n_pad, s=s_l,
                        bn=cfg.bn, bk=cfg.bk, use_kernel=use_kernel,
                        interpret=interpret)[0]
            elif tropical:
                if use_kernel:
                    K = kernel_registry.get("tropical").forms
                    bs = min(s_l, 128)

                    def partial_nd(fd_k, d):
                        _, nd = K["dense"](fd_k, dense_l, d, w_min, bs=bs,
                                           bn=cfg.bn, bk=cfg.bk,
                                           interpret=interpret)
                        return nd
                else:
                    def partial_nd(fd_k, d):
                        cand = S.minplus_candidates(fd_k, dense_l,
                                                    chunk=cfg.chunk)
                        return jnp.minimum(d, cand)

                if vertex_sharded:
                    def dense_form(f, d, p, step):
                        k0 = jax.lax.axis_index(MODEL_AXIS) * nk
                        f_k = jax.lax.dynamic_slice_in_dim(f, k0, nk, 1)
                        d_k = jax.lax.dynamic_slice_in_dim(d, k0, nk, 1)
                        fd_k = jnp.where(f_k != 0, d_k, INF)
                        # ⊕ = min: exact cross-shard combine of partials
                        nd = jax.lax.pmin(partial_nd(fd_k, d), MODEL_AXIS)
                        return (nd < d).astype(jnp.int8), nd, p
                else:
                    def dense_form(f, d, p, step):
                        fd = jnp.where(f != 0, d, INF)
                        nd = partial_nd(fd, d)
                        return (nd < d).astype(jnp.int8), nd, p
            else:
                # the kernel push is bit-packed: pack the transpose of
                # the local K-row block (C == 1: the full pull operand)
                # once per trace — word-exact vs graph.to_pull_packed
                adj_pull_l = pack_bits(jnp.transpose(dense_l) != 0) \
                    if use_kernel else jnp.zeros((1, 1), jnp.uint32)
                push = S.boolean_forms(
                    dense_l, adj_pull_l, None, n_pad=n_pad, s=s_l,
                    bn=cfg.bn, bk=cfg.bk, use_kernel=use_kernel,
                    interpret=interpret)[S.PUSH]
                if vertex_sharded:
                    def dense_form(f, d, p, step):
                        k0 = jax.lax.axis_index(MODEL_AXIS) * nk
                        f_k = jax.lax.dynamic_slice_in_dim(f, k0, nk, 1)
                        new_p, _, _ = push(f_k, d, p, step)
                        # ⊕ = OR: any shard's partial discovery counts
                        new = or_combine(new_p)
                        return new, jnp.where(new != 0, step, d), p
                else:
                    dense_form = push
                    if cfg.fused_steps and use_kernel \
                            and cfg.mode == "dense":
                        fused_steps_l = S.resolve_fused_steps(
                            "boolean", "push",
                            fused_steps=cfg.fused_steps,
                            max_steps=cfg.max_sweeps or n_real,
                            use_kernel=True, n_pad=n_pad,
                            bs=min(s_l, 128),
                            budget=None if cfg.tuning is None
                            else cfg.tuning.vmem_budget) or 0
                    if fused_steps_l:
                        fused = S.fused_form(
                            "boolean", adj_pull_l, "push",
                            bs=min(s_l, 128), max_sweeps=fused_steps_l,
                            interpret=interpret)

                        def fused_combine(prod, stopped):
                            # like `converged`: the fused block's scalars
                            # must agree on every shard so each shard's
                            # while_loop takes the same trip count
                            prod = jax.lax.pmax(prod, all_axes)
                            alive = jax.lax.psum(
                                (~stopped).astype(jnp.int32), all_axes)
                            return prod, alive == 0

        # ---- sparse form: scatter-⊕ over the shard's lanes (rows) ---
        sparse_form = None
        if cfg.need_sparse:
            if counting:
                if vertex_sharded:
                    def sparse_form(f, ds, p, step):
                        # each edge lives in exactly one shard partition,
                        # so the local scatter-adds psum to the exact
                        # per-node path count
                        d, sg = ds
                        # one lane gather (see sweep.tropical_forms)
                        fs = jnp.where(f != 0, sg, 0.0)
                        cand_p = jnp.zeros(d.shape, jnp.float32).at[
                            ..., dst_e].add(fs[..., src_e])
                        new, ds2 = counting_epilogue(cand_p, d, sg, step)
                        return new, ds2, p
                else:
                    sparse_form = S.counting_forms(
                        jnp.zeros((1, 1), jnp.int8), src_e, dst_e,
                        n_pad=n_pad, s=s_l, use_kernel=False,
                        interpret=interpret)[1]
            elif tropical:
                _, sparse_c = S.tropical_forms(
                    None, src_e, dst_e, w_e, n_pad=n_pad, chunk=cfg.chunk,
                    use_kernel=use_kernel, interpret=interpret, eb=cfg.eb)
                if vertex_sharded:
                    def sparse_form(f, d, p, step):
                        _, nd_p, _ = sparse_c(f, d, p, step)
                        nd = jax.lax.pmin(nd_p, MODEL_AXIS)
                        return (nd < d).astype(jnp.int8), nd, p
                else:
                    sparse_form = sparse_c
            else:
                sparse_c = S.boolean_forms(
                    jnp.zeros((1, 1), jnp.int8),
                    jnp.zeros((1, 1), jnp.uint32), (row_src, row_dst),
                    n_pad=n_pad, s=s_l, use_kernel=False,
                    interpret=interpret)[S.SPARSE]
                if vertex_sharded:
                    def sparse_form(f, d, p, step):
                        new_p, _, _ = sparse_c(f, d, p, step)
                        new = or_combine(new_p)
                        return new, jnp.where(new != 0, step, d), p
                else:
                    sparse_form = sparse_c

        forms = (dense_form or sparse_form, sparse_form or dense_form)

        choose = None
        if cfg.mode == "auto":
            bs = min(s_l, 128)

            def choose(st: S.SweepState):
                d = st.dist[0] if counting else st.dist
                stats = frontier_stats(
                    st.frontier, d, bs=bs, bn=128, bk=128,
                    unreached=jnp.isinf(d) if tropical else None)
                live = stats.live_tile_frac
                if dp:
                    # the lax.switch predicate must agree on every shard
                    # or the collectives inside the forms deadlock
                    live = jax.lax.pmean(live, dp)
                dense_c = cfg.c_dense * s_l * nk * n_pad * live
                sparse_c_ = jnp.float32(cfg.c_sparse * s_l * m_local)
                return (dense_c > sparse_c_).astype(jnp.int32)

        def converged(new):
            # Fact 1 must fire everywhere at once: reduce over the whole
            # mesh so every shard's while_loop predicate agrees
            return jax.lax.psum(jnp.any(new != 0).astype(jnp.int32),
                                all_axes) == 0

        state0 = (dist0_l, sigma0_l) if counting else dist0_l
        st = S.sweep_loop(forms, S.make_state(f0_l, state0, n_forms=2),
                          max_steps=steps, choose=choose, deg=deg_l,
                          forced_dir=0 if cfg.mode in ("auto", "dense")
                          else 1,
                          converged=converged,
                          fused=fused, fused_steps=fused_steps_l,
                          fused_combine=fused_combine)
        if counting:
            dist_out, sigma_out = st.dist
        else:
            dist_out, sigma_out = st.dist, sigma0_l
        # per-shard partials are exact integer sums in f32, so the
        # psum'd Eq. 10 counter matches any row partition bit-for-bit;
        # the frontier rows are replicated over MODEL, so the dp-psum
        # already agrees on every model shard
        edges = jax.lax.psum(st.edges_touched, dp) if dp \
            else st.edges_touched
        return dist_out, sigma_out, st.step, st.dir_counts, edges

    row_spec = P(dp, None) if dp else P(None, None)
    dense_spec = P(MODEL_AXIS, None) \
        if (vertex_sharded and cfg.need_dense) else P()
    boolean = not (tropical or counting)
    lanes_sharded = vertex_sharded and cfg.need_sparse
    lane_spec = P(MODEL_AXIS, None) \
        if (lanes_sharded and not boolean) else P()
    w_spec = lane_spec if tropical else P()   # boolean w_l is a 1-D dummy
    rows_sharded = lanes_sharded and boolean
    row_src_spec = P(MODEL_AXIS, None, None) if rows_sharded else P()
    row_dst_spec = P(MODEL_AXIS, None) if rows_sharded else P()

    sharded = jax.shard_map(
        run_local, mesh=mesh,
        in_specs=(dense_spec, lane_spec, lane_spec, w_spec, row_src_spec,
                  row_dst_spec, P(), P(), row_spec, row_spec, row_spec,
                  P()),
        out_specs=(row_spec, row_spec, P(), P(), P()),
        check_vma=False)

    @jax.jit
    def runner(dense_op, src_l, dst_l, w_l, row_src, row_dst, w_min, deg,
               sources, n_valid, steps):
        s_pad = sources.shape[0]
        f0 = one_hot_frontier(sources, n_pad, dtype=jnp.int8)
        row_ok = (jnp.arange(s_pad) < n_valid)[:, None]
        f0 = jnp.where(row_ok, f0, 0)
        if tropical:
            # pad rows/cols stay +inf with empty frontiers: inert
            dist0 = jnp.where(f0 != 0, 0.0, jnp.full((s_pad, n_pad), INF))
        else:
            dist0 = jnp.where(f0 != 0, 0,
                              jnp.full((s_pad, n_pad), UNREACHED))
            # pad rows/cols are born "visited" — same trick as the engine
            dist0 = jnp.where(
                row_ok & (jnp.arange(n_pad)[None, :] < n_real), dist0, 0)
        if counting:
            sigma0 = jnp.where(f0 != 0, 1.0, 0.0).astype(jnp.float32)
        else:
            # inert row-sharded dummy so the shard_map arity stays fixed
            sigma0 = jnp.zeros((s_pad, 1), jnp.float32)
        return sharded(dense_op, src_l, dst_l, w_l, row_src, row_dst, w_min,
                       deg, f0, dist0, sigma0, steps)

    return runner


# --------------------------------------------------------------------------
# public entry point
# --------------------------------------------------------------------------

def sharded_apsp(g: Union[CSRGraph, ShardedOperands],
                 sources: Optional[Sequence[int]] = None, *,
                 mesh: Optional[Mesh] = None, weights=None,
                 config: Optional[ShardedConfig] = None
                 ) -> ShardedApspResult:
    """Multi-device batched APSP through the semiring sweep layer.

    Pass a :class:`ShardedOperands` (from :func:`prepare_sharded`) to
    reuse device-resident operands across calls; otherwise a
    :class:`CSRGraph` plus ``mesh`` (and ``weights`` for the tropical
    semiring).  Sources are padded up to the data-parallel extent and
    distances/sweep counts come back bit-identical to the single-device
    ``apsp_engine`` / ``weighted_apsp``.
    """
    if isinstance(g, ShardedOperands):
        if mesh is not None or weights is not None or config is not None:
            raise ValueError(
                "sharded_apsp: mesh=/weights=/config= are baked into the "
                "prepared ShardedOperands — passing them alongside would "
                "be silently ignored; call prepare_sharded again instead")
        ops = g
    else:
        if mesh is None:
            raise ValueError("sharded_apsp needs mesh= (or prepared "
                             "ShardedOperands)")
        ops = prepare_sharded(g, mesh, weights=weights,
                              config=config or ShardedConfig())
    graph, cfg = ops.graph, ops.config
    n = graph.n_nodes
    srcs = np.arange(n, dtype=np.int32) if sources is None else \
        np.asarray(sources, np.int32)
    if srcs.size == 0:
        raise ValueError("sharded_apsp: empty source list")
    if srcs.min() < 0 or srcs.max() >= n:
        raise ValueError(
            f"sharded_apsp: sources must be in [0, {n}), got "
            f"[{srcs.min()}, {srcs.max()}]")
    D = dp_extent(ops.mesh)
    # every dp shard gets the same multiple-of-8 (kernel-tileable) row
    # count; above one source tile the local rows must tile by 128
    s_pad = _round_up(len(srcs), D * 8)
    if s_pad // D > 128:
        s_pad = _round_up(s_pad, D * 128)
    padded = np.zeros(s_pad, np.int32)
    padded[: len(srcs)] = srcs

    use_kernel, interpret = _resolve_kernel(cfg)
    runner = _make_runner(ops.mesh, cfg, ops.n_pad, n, ops.m_local,
                          use_kernel, interpret, ops.n_shards)
    dist, sigma, step, dir_counts, edges = runner(
        ops.dense_op, ops.src_l, ops.dst_l, ops.w_l, ops.row_src,
        ops.row_dst, ops.w_min, ops.deg,
        jnp.asarray(padded), jnp.int32(len(srcs)),
        jnp.int32(cfg.max_sweeps or n))
    return ShardedApspResult(dist=dist[: len(srcs), :n], sweeps=step,
                             direction_counts=dir_counts,
                             sigma=sigma[: len(srcs), :n]
                             if cfg.counting else None,
                             edges_touched=edges)
