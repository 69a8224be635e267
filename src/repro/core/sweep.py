"""The semiring sweep-operator layer — one loop under every DAWN path.

Every bound in the paper (Eqs. 5/10) falls out of a single mechanism: a
*sweep* operator that extends all known shortest paths by one relaxation,
skips already-settled targets (Thm 3.2), and stops at the first sweep that
settles nothing (Fact 1).  Algebraic BFS (Burkhardt 2019) and the paper's
own §5 weighted outlook say the same thing: the machinery is a *semiring*
iteration

    dist' = dist (+)  frontier-restricted ( dist (x) A )

with (+, x) = (∨, ∧) for unweighted BFS, (min, +) for non-negative
weights, (min, id) for label propagation, and (add-on-dist-ties, ·) for
shortest-path counting (the Brandes/betweenness substrate — the one
non-idempotent ⊕ in the set).  This module owns:

  * :class:`Semiring`    — the algebra spec (boolean / tropical / min-label);
  * the three sweep *forms* over identical padded state — dense push GEMM
    (:func:`boolean_forms`/:func:`tropical_forms` ``[PUSH]``), bit-packed
    pull (boolean only), and edge-parallel sparse scatter;
  * :class:`SweepState`  — the unified loop state (``frontier``, ``dist``,
    ``parent``, ``step``, ``sweeps``, ``edges_touched``, ``dir_counts``);
  * :func:`sweep_loop`   — the ONE ``lax.while_loop`` driver in the repo's
    core: every layer (bovm/sovm/bfs/weighted/wcc/distributed/engine)
    instantiates it with a semiring's forms instead of carrying its own
    loop;
  * :func:`derive_parents` — shortest-path-tree post-pass shared by the
    batched paths that do not track parents in-loop;
  * :func:`time_sweep_forms` — the wall-clock calibration primitive behind
    the CPU-path direction choice (see core/engine.py).

A *form* is a callable ``(frontier, dist, parent, step) -> (new_frontier,
dist, parent)``.  ``new_frontier`` is the set of entries improved by the
sweep (int8/bool); Fact-1 convergence is ``~any(new_frontier)`` — for the
boolean semiring "nothing newly discovered", for the tropical semiring
"no distance improved", for min-label "no label lowered".  Forms are
shape-polymorphic over the leading axes: the batched engine runs (S, n)
state, the single-source paths run (n+1,) sentinel-padded state.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..kernels import common as kernel_common
from ..kernels import registry as kernel_registry
from .frontier import UNREACHED, WORD, pack_bits

PUSH, PULL, SPARSE = 0, 1, 2
DIRECTION_NAMES = ("push", "pull", "sparse")

INF = jnp.float32(jnp.inf)


# --------------------------------------------------------------------------
# semiring specs
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Semiring:
    """Algebra spec for a sweep: which (⊕, ⊗) the forms implement.

    ``unreached`` is the ⊕-identity stored for "no path yet"; ``source_dist``
    the ⊗-identity stored at the sources.  The cost-model ``unit`` names
    what one modelled cost count means for this semiring (the engine's
    cost constants are per-unit, see docs/ARCHITECTURE.md).
    """
    name: str
    dist_dtype: Any
    unreached: Any
    source_dist: Any
    unit: str

    def unreached_mask(self, dist: jax.Array) -> jax.Array:
        """Boolean mask of not-yet-settled entries (the Thm 3.2 skip set
        and the pull/push occupancy signal)."""
        if self.name == "tropical":
            return jnp.isinf(dist)
        return dist == jnp.asarray(self.unreached, dist.dtype)


BOOLEAN = Semiring("boolean", jnp.int32, -1, 0,
                   unit="MXU MAC / uint32 word / CSR lane")
TROPICAL = Semiring("tropical", jnp.float32, float("inf"), 0.0,
                    unit="f32 add+min lane / CSR relax lane")
MIN_LABEL = Semiring("min_label", jnp.int32, None, None,
                     unit="CSR min-scatter lane")
# Path counting (Burkhardt's algebraic-BFS companion semiring): the state
# is the PAIR (dist int32, sigma f32) and ⊕ is elementwise ADD of path
# counts, gated on dist-improvement ties — the first non-idempotent ⊕ in
# the repo (OR∘OR = OR and min∘min = min, but add∘add ≠ add), which is
# why the sharded reduction must mask partials before summing (see
# core/distributed.py) instead of just folding epilogue outputs.
COUNTING = Semiring("counting", jnp.int32, -1, 0,
                    unit="f32 MAC / CSR add lane")

SEMIRINGS = {s.name: s for s in (BOOLEAN, TROPICAL, MIN_LABEL, COUNTING)}


# --------------------------------------------------------------------------
# unified loop state + the single while_loop driver
# --------------------------------------------------------------------------

class SweepState(NamedTuple):
    """Loop state shared by every semiring / form / execution path."""
    frontier: jax.Array       # entries improved by the last sweep (int8/bool)
    dist: jax.Array           # distances / labels (semiring dist_dtype)
    parent: jax.Array         # shortest-path tree (int32; (1,) dummy if off)
    step: jax.Array           # scalar int32 — sweeps executed
    done: jax.Array           # scalar bool — Fact 1 fired
    sweeps: jax.Array         # scalar int32 — last *productive* step (= ε)
    edges_touched: jax.Array  # scalar float32 — Eq. 10 useful-work counter
    dir_counts: jax.Array     # (n_forms,) int32 — sweeps run per form


SweepForm = Callable[[jax.Array, jax.Array, jax.Array, jax.Array],
                     Tuple[jax.Array, jax.Array, jax.Array]]


def make_state(frontier: jax.Array, dist: jax.Array,
               parent: Optional[jax.Array] = None, *,
               n_forms: int = 3) -> SweepState:
    """Initial SweepState around caller-built frontier/dist buffers."""
    if parent is None:
        parent = jnp.zeros((1,), jnp.int32)
    return SweepState(frontier=frontier, dist=dist, parent=parent,
                      step=jnp.int32(0), done=jnp.bool_(False),
                      sweeps=jnp.int32(0),
                      edges_touched=jnp.float32(0.0),
                      dir_counts=jnp.zeros(n_forms, jnp.int32))


def sweep_loop(forms: Sequence[SweepForm], state: SweepState, *,
               max_steps, deg: Optional[jax.Array] = None,
               choose: Optional[Callable[[SweepState], jax.Array]] = None,
               forced_dir: int = 0,
               converged: Optional[Callable[[jax.Array], jax.Array]] = None,
               fused: Optional[Callable] = None, fused_steps: int = 0,
               fused_combine: Optional[Callable] = None,
               ) -> SweepState:
    """THE sweep driver — the only ``lax.while_loop`` under repro/core.

    forms      : candidate sweep forms; one runs per iteration.
    max_steps  : static or traced sweep bound (diameter / hop bound).
    deg        : optional out-degree vector; when given, each sweep adds
                 sum(deg[frontier]) to ``edges_touched`` (Eq. 10).
    choose     : traced ``SweepState -> int32`` form index (the per-sweep
                 direction optimizer, dispatched through ``lax.switch``);
                 ``None`` pins ``forms[forced_dir]`` at trace time.
    converged  : Fact-1 test over the new frontier; default
                 ``~any(new)``.  The distributed path overrides it with a
                 psum so all shards agree on termination.
    fused      : optional fused multi-sweep block ``(frontier, dist, step,
                 n_run) -> (new, dist, prod, stopped)`` built by
                 :func:`fused_form` — each loop iteration then executes up
                 to ``fused_steps`` sweeps inside ONE persistent kernel
                 (Fact 1 in-kernel), and the body reconstructs the exact
                 per-sweep accounting from the kernel's (productive-count,
                 converged) pair: a tile's productivity is prefix-
                 contiguous, so the block executed ``prod + 1`` sweeps if
                 it converged and ``n_run`` otherwise.  ``step``,
                 ``sweeps``, ``done``, ``dir_counts`` and the final
                 frontier/dist are bit-identical to the per-sweep path;
                 only ``edges_touched`` is not tracked (stays at its prior
                 value — the fused kernel never materializes per-sweep
                 frontiers to weigh against ``deg``).  ``choose`` must be
                 None (fusion pins one direction).
    fused_combine : optional cross-shard reduction of the block's
                 ``(prod, stopped)`` pair (pmax / psum-all) so every
                 shard of the distributed executor agrees on the loop
                 accounting — the fused analogue of ``converged``.

    Each form call, the fused block, ``choose`` and the convergence test
    trace under a ``dawn.sweep.*`` scope (:mod:`repro.obs`), so a device
    trace attributes every sweep operation to its form.
    """
    forms = tuple(obs.scoped_form(f) for f in forms)

    def cond(st: SweepState):
        return (~st.done) & (st.step < max_steps)

    if fused is not None:
        assert choose is None, "fused blocks pin one direction"

        def body(st: SweepState):
            n_run = jnp.minimum(jnp.asarray(fused_steps, jnp.int32),
                                jnp.asarray(max_steps, jnp.int32) - st.step)
            with obs.scope("sweep.fused"):
                new, dist, prod, stopped = fused(st.frontier, st.dist,
                                                 st.step, n_run)
                if fused_combine is not None:
                    prod, stopped = fused_combine(prod, stopped)
            executed = jnp.where(stopped, prod + 1, n_run)
            return SweepState(
                frontier=new, dist=dist, parent=st.parent,
                step=st.step + executed, done=stopped,
                sweeps=jnp.where(prod > 0, st.step + prod, st.sweeps),
                edges_touched=st.edges_touched,
                dir_counts=st.dir_counts.at[jnp.int32(forced_dir)]
                                        .add(executed))
    else:
        def body(st: SweepState):
            step = st.step + 1
            if choose is None:
                idx = jnp.int32(forced_dir)
                new, dist, parent = forms[forced_dir](st.frontier, st.dist,
                                                      st.parent, step)
            else:
                with obs.scope("sweep.choose"):
                    idx = choose(st)
                new, dist, parent = jax.lax.switch(idx, forms, st.frontier,
                                                   st.dist, st.parent, step)
            with obs.scope("sweep.test"):
                if converged is None:
                    stop = ~jnp.any(new != 0)
                else:
                    stop = converged(new)
                touched = st.edges_touched
                if deg is not None:
                    touched = touched + jnp.sum(
                        (st.frontier != 0).astype(jnp.float32) * deg)
            return SweepState(
                frontier=new, dist=dist, parent=parent, step=step, done=stop,
                sweeps=jnp.where(stop, st.sweeps, step),
                edges_touched=touched,
                dir_counts=st.dir_counts.at[idx].add(1))

    return jax.lax.while_loop(cond, body, state)


# --------------------------------------------------------------------------
# fused multi-sweep dispatch (the persistent-kernel capability seam)
# --------------------------------------------------------------------------

def resolve_fused_steps(semiring, form: str, *, fused_steps: int,
                        max_steps: int, use_kernel: bool, n_pad: int,
                        bs: int, budget: Optional[int] = None
                        ) -> Optional[int]:
    """Static fused-block length for an engine run, or ``None`` for the
    per-sweep path.  ``fused_steps`` is the engine config's request: 0 =
    off, -1 = whole fixpoint per invocation, K > 0 = K-sweep blocks.
    Fusion engages only on the kernel path, only when the semiring
    registers a fused form for ``form``, and only when the fused kernel's
    whole-operand VMEM residency (``vmem_bytes(form="fused")``) fits the
    per-core budget — oversized graphs silently fall back to per-sweep
    dispatch rather than blowing VMEM.  ``budget`` overrides the static
    default (engines pass their TuningPlan's per-device figure)."""
    if not fused_steps or not use_kernel or not kernel_registry.has(semiring):
        return None
    ks = kernel_registry.get(semiring)
    if form not in ks.fused_forms:
        return None
    if ks.vmem_bytes(form="fused", bs=bs, n=n_pad) > \
            kernel_common.vmem_limit(budget):
        return None
    return max_steps if fused_steps < 0 else min(fused_steps, max_steps)


def fused_form(semiring, operand, form: str, *, bs: int, max_sweeps: int,
               interpret: bool = True) -> Callable:
    """Close a registered fused multi-sweep kernel over its operand —
    the fused analogue of the per-sweep form closures.  The result has
    the ``sweep_loop(fused=...)`` contract: ``(frontier, dist, step,
    n_run) -> (new, dist, prod, stopped)``, where ``dist`` is the loop
    state's dist slot (the (dist, sigma) pair for counting)."""
    kern = kernel_registry.get(semiring).fused_forms[form]

    def fused(f, state, step, n_run):
        return kern(f, operand, state, step, n_run, bs=bs,
                    max_sweeps=max_sweeps, interpret=interpret)

    return fused


# --------------------------------------------------------------------------
# boolean semiring forms (unweighted BFS — paper Algs. 1/2)
# --------------------------------------------------------------------------

# Destination rows: the boolean sparse form's operand.  Each vertex's
# in-lanes, in CSC order, are cut into rows of W; a sweep ORs each row
# densely and scatters one update per row (m/W + n sorted indices) instead
# of one per lane (m unsorted ones, sorted again every sweep).
ROW_WIDTH = 8


def row_width(m_pad: int, n_real: int) -> int:
    """W of a graph's destination rows: :data:`ROW_WIDTH` where its lanes
    average at least 16 a vertex, else 1 (each lane a row of its own,
    still in sorted destination order).

    Rows of 8 gather up to 7 pad slots a vertex to scatter 8 times fewer
    indices.  On a v5e with a 64 MB frontier table a gathered slot costs
    about 1.8 sorted scatter indices, so rows of 8 pay from about 16
    lanes a vertex: a 1024² grid (4 a vertex) sweeps 3.0× faster at
    W = 1 than at 8, a scale-15 Kronecker graph (27) 2.7× faster at 8.
    """
    return ROW_WIDTH if m_pad >= 16 * n_real else 1


def row_count(m_pad: int, n_real: int) -> int:
    """Rows of the destination-row layout of ``m_pad`` lanes over
    ``n_real`` vertices (see :func:`dst_rows`)."""
    return _row_count(m_pad, n_real, row_width(m_pad, n_real))


def _row_count(m_pad: int, n_real: int, width: int) -> int:
    # sum ceil(deg_in / width), bounded from the static shapes alone so
    # every graph of one (m_pad, n_real) compiles to one shape
    rows = -(-(m_pad + (width - 1) * n_real) // width)
    return -(-rows // 128) * 128


def dst_rows(indptr_t: jax.Array, indices_t: jax.Array, *,
             n_real: int) -> Tuple[jax.Array, jax.Array]:
    """The destination-row layout of a graph's CSC lanes, built on the
    device (once per graph: ``PreparedGraph.rows`` caches it).

    -> ``row_src`` (W, R) int32: for each destination v, its in-lane
       sources in CSC order over ``ceil(deg_in(v) / W)`` rows, slot w of
       row r at ``[w, r]``;
       ``row_dst`` (R,) int32: each row's destination, ascending.
    Empty slots and the trailing pad rows hold the pad vertex ``n_real``,
    which no sweep ever activates (it is born visited).
    ``W = row_width(m_pad, n_real)`` and ``R = row_count(m_pad, n_real)``.
    The slot axis leads because the TPU tiles an array's two minor axes
    by (8, 128): an (R, 8) int32 array would take 16 times its size.
    """
    return _dst_rows(indptr_t, indices_t, n_real=n_real,
                     width=row_width(indices_t.shape[0], n_real))


@functools.partial(jax.jit, static_argnames=("n_real", "width"))
def _dst_rows(indptr_t, indices_t, *, n_real: int, width: int):
    r_pad = _row_count(indices_t.shape[0], n_real, width)
    deg = indptr_t[1:] - indptr_t[:-1]                       # (n_real,)
    row_end = jnp.cumsum((deg + width - 1) // width, dtype=jnp.int32)
    # a row's destination: how many vertices' rows end at or before it
    # (n_real on the pad rows)
    row_dst = jnp.cumsum(jnp.zeros(r_pad, jnp.int32).at[row_end].add(
        1, mode="drop"), dtype=jnp.int32)
    # per-vertex tables with one more entry, for the pad vertex
    first_row = jnp.append(row_end - (deg + width - 1) // width, 0)
    deg = jnp.append(deg, 0)
    k = (jnp.arange(width, dtype=jnp.int32)[:, None]         # slot in v
         + (jnp.arange(r_pad, dtype=jnp.int32) - first_row[row_dst]) * width)
    lane = indptr_t[row_dst] + k
    row_src = jnp.where(k < deg[row_dst],
                        indices_t.at[lane].get(mode="clip"), n_real)
    return row_src, row_dst


# Widest vertex axis one row scatter writes.  On a v5e, XLA lays a
# (64, n) bool scatter target out key-major from n = 2^21 on and takes a
# slow scatter path there, with a copy on each side: 1.79 s a sweep at
# Graph500 scale 21 against 40 ms at scale 20.  Pieces of at most 1.5 x
# 2^20 vertices keep the vertex-major layout and the fast path.
SCATTER_COLUMNS = 3 << 19


def row_scatter_or(shape, row_dst, rows_or) -> jax.Array:
    """Bool ``shape`` (..., n): each row's OR, ``rows_or`` (..., R), ORed
    into its destination at the sorted ``row_dst`` (R,) — in pieces of
    the vertex axis of at most :data:`SCATTER_COLUMNS`.  A piece takes
    every row: rows before it land in a margin of 128 columns on its
    left, rows after it in one on its right, and both margins are cut
    off.  That keeps the indices sorted and ``rows_or`` unmasked: XLA
    fuses a mask on it into the relayout of the packed gather's rows
    (:func:`packed_row_or`), once for each piece."""
    n = shape[-1]
    pieces = -(-n // SCATTER_COLUMNS)
    if pieces == 1:
        return jnp.zeros(shape, jnp.bool_).at[..., row_dst].max(
            rows_or, indices_are_sorted=True)
    width = -(-n // pieces // 128) * 128
    parts = []
    for lo in range(0, n, width):
        w = min(width, n - lo)
        idx = jnp.clip(row_dst - lo + 128, 127, w + 128)
        parts.append(jnp.zeros(shape[:-1] + (w + 256,), jnp.bool_).at[
            ..., idx].max(rows_or, indices_are_sorted=True)[..., 128:w + 128])
    return jnp.concatenate(parts, axis=-1)


# Largest byte frontier table, keys x n_pad, that the sparse form gathers
# from as it is.  On a v5e XLA keeps kron_s15's 4.2 MB table in VMEM (a
# gathered slot ~1.8 ns) but Graph500 scale 20's 64 MiB one in HBM (11.3
# ns); past this size the form gathers from the frontier packed 32 keys a
# uint32 word, a table 8 times smaller (8 MiB at scale 20), which XLA
# places in VMEM.
PACK_GATHER_BYTES = 16 << 20


def gather_words(shape) -> int:
    """uint32 words a vertex that the sparse form gathers for a frontier
    of ``shape`` (keys, n): ceil(keys / 32) where its byte table passes
    :data:`PACK_GATHER_BYTES`, else 0 (it gathers the bytes).  1-D
    single-source state has no key axis and is never packed."""
    if len(shape) != 2 or shape[0] * shape[1] <= PACK_GATHER_BYTES:
        return 0
    return -(-shape[0] // WORD)


def gather_table_bytes(shape) -> int:
    """Bytes of the table the sparse form gathers from (see
    :func:`gather_words`)."""
    words = gather_words(shape)
    return 4 * words * shape[-1] if words else math.prod(shape)


def packed_row_or(f, row_src, words: int) -> jax.Array:
    """Each destination row's OR of the (keys, n) frontier ``f``, as
    (keys, R) bool, gathered from ``f`` packed to (words, n) uint32 words
    (key 32w + b at bit b of word w; the keys padded with zeros to a
    whole word).  The vertex axis stays minor, so at 64 keys the table
    is ``u32[2, n]``; the pack goes through bytes, 8 keys an octet, so
    that XLA's one relayout copy of the key axis is of bytes, not of
    words (a v5e lays the (keys, n) state out key-minor)."""
    keys, n = f.shape
    bits = jnp.pad((f != 0).astype(jnp.uint8),
                   ((0, words * WORD - keys), (0, 0)))
    shifts = jnp.arange(8, dtype=jnp.uint8)[:, None]
    octets = jnp.sum(bits.reshape(words * 4, 8, n) << shifts, axis=1,
                     dtype=jnp.uint8)                    # (4 words, n)
    shifts = 8 * jnp.arange(4, dtype=jnp.uint32)[:, None]
    table = jnp.sum(octets.reshape(words, 4, n).astype(jnp.uint32) << shifts,
                    axis=1, dtype=jnp.uint32)            # (words, n)
    rows = jax.lax.reduce(table[:, row_src], jnp.uint32(0),
                          jax.lax.bitwise_or, (1,))      # (words, R)
    shifts = jnp.arange(WORD, dtype=jnp.uint32)[:, None]
    bits = (rows[:, None, :] >> shifts) & jnp.uint32(1)  # (words, 32, R)
    return bits.reshape(words * WORD, -1)[:keys] != 0


def _pull_chunk_size(n_pad: int, preferred: int) -> int:
    for c in (preferred, 512, 256, 128):
        if c <= n_pad and n_pad % c == 0:
            return c
    return n_pad


def boolean_forms(adj, adj_pull, rows, *, n_pad: int, s: int,
                  bn: int = 128, bk: int = 128, pull_chunk: int = 512,
                  use_kernel: bool = False, interpret: bool = True,
                  track_parent: bool = False,
                  accum_dtype=jnp.float32) -> Tuple[SweepForm, ...]:
    """(push, pull, sparse) boolean sweep forms over identical state —
    the single source of truth for what each direction dispatches, shared
    by the batch driver, the single-source paths, and the calibration
    measurement.

    ``rows`` is the sparse form's operand, the graph's :func:`dst_rows`
    layout ``(row_src, row_dst)``: the form gathers the frontier at
    ``row_src``, ORs each row and scatters one update per row at the
    sorted ``row_dst``.  ``adj``/``adj_pull``/``rows`` may be dummies
    (``rows`` may be ``None``) when the caller has resolved a form that
    never dispatches the others (a pinned ``forced_dir`` traces only its
    own operands); ``n_pad`` is therefore passed explicitly rather than
    read off ``adj``.  ``track_parent`` maintains the shortest-path tree
    in-loop on the sparse form (any active in-neighbor, max src id wins —
    the same tie-break :func:`derive_parents` applies as a post-pass).

    ``use_kernel`` swaps the push/pull closures for the boolean Pallas
    kernels looked up in :mod:`repro.kernels.registry`.  BOTH kernel
    directions read the bit-packed ``adj_pull`` operand (the kernel push
    is the packed word-AND/OR sweep — no f32 GEMM on the boolean kernel
    path); ``adj`` feeds only the XLA reference push.
    """
    bs = min(s, 128)
    chunk = _pull_chunk_size(n_pad, pull_chunk)
    wk = kernel_common.word_tile(max(n_pad // 32, 1))

    if use_kernel:
        K = kernel_registry.get(BOOLEAN).forms
        # The kernel push is bit-packed (paper Eq. 13): it drives the SAME
        # word-AND/OR math as pull over ``adj_pull`` — whose word width may
        # be rectangular (a sharded K-row block packs n/C contraction rows)
        # — so its word tile comes off the operand, not n_pad.  The f32
        # GEMM push survives as the registry's "push_f32" form.
        wk_push = kernel_common.word_tile(adj_pull.shape[1])

        def push(f, d, p, step):
            new, dist = K["push"](pack_bits(f != 0), adj_pull, d, step,
                                  bs=bs, bn=bn, wk=wk_push,
                                  interpret=interpret)
            return new, dist, p

        def pull(f, d, p, step):
            new, dist = K["pull"](pack_bits(f != 0), adj_pull, d,
                                  step, bs=min(s, 8), bn=bn, wk=wk,
                                  interpret=interpret)
            return new, dist, p
    else:
        def push(f, d, p, step):
            counts = jax.lax.dot_general(
                f.astype(accum_dtype), adj.astype(accum_dtype),
                (((f.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=accum_dtype)
            new = (counts > 0) & (d == UNREACHED)
            return new.astype(jnp.int8), jnp.where(new, step, d), p

        def pull(f, d, p, step):
            # chunked oracle for the packed pull sweep — bounds the
            # (S, C, W) broadcast intermediate to ~chunk * S * W words
            fp = pack_bits(f != 0)                       # (S, W)
            blocks = adj_pull.reshape(n_pad // chunk, chunk, -1)

            def one(block):                              # (C, W) uint32
                return jnp.any(fp[:, None, :] & block[None], axis=-1)

            hits = jnp.moveaxis(jax.lax.map(one, blocks), 0, 1)
            hits = hits.reshape(f.shape)
            new = hits & (d == UNREACHED)
            return new.astype(jnp.int8), jnp.where(new, step, d), p

    def sparse(f, d, p, step):
        # batched SOVM sweep (paper Alg. 2 / Eq. 9 union as scatter-OR)
        row_src, row_dst = rows
        # the parent tree needs each slot's activity: it gathers bytes
        words = 0 if track_parent else gather_words(f.shape)
        if words:
            rows_or = packed_row_or(f, row_src, words)
        else:
            # compared before the gather: one pass over (S, n), not over
            # (S, W, R) (on a v5e 10% of the sweep at 2^15 vertices)
            active = (f != 0)[..., row_src]              # (..., W, R)
            rows_or = jnp.any(active, axis=-2)
        hits = row_scatter_or(d.shape, row_dst, rows_or)
        new = hits & (d == UNREACHED)
        if track_parent:
            pcand = jnp.full(d.shape, -1, jnp.int32).at[..., row_dst].max(
                jnp.max(jnp.where(active, row_src, -1), axis=-2),
                indices_are_sorted=True)
            p = jnp.where(new, pcand, p)
        return new.astype(jnp.int8), jnp.where(new, step, d), p

    return push, pull, sparse


# --------------------------------------------------------------------------
# tropical semiring forms (weighted SSSP — paper §5 extension)
# --------------------------------------------------------------------------

def tropical_forms(wdense, src_idx, dst_idx, w_edges, *,
                   n_pad: int = 0, chunk: int = 128,
                   use_frontier: bool = True,
                   use_kernel: bool = False, interpret: bool = True,
                   bn: int = 128, bk: int = 128,
                   eb: int = 128) -> Tuple[SweepForm, ...]:
    """(dense, sparse) (min,+) sweep forms.

    dense  — the f32 min-plus GEMM-analogue of the boolean push sweep:
             ``cand[s, j] = min_k (dist[s, k] + W[k, j])`` over frontier
             rows.  ``wdense`` is (n_pad, n_pad) f32 with +inf non-edges
             (pass ``None`` when only the sparse form runs).  Reference
             path: ``chunk`` destination columns per ``lax.map`` step so
             the (S, chunk, n) broadcast stays bounded.  Kernel path
             (``use_kernel=True``): the fused Pallas min-plus sweep with
             settled-bound tile skipping, looked up in
             :mod:`repro.kernels.registry` exactly as
             :func:`boolean_forms` does.
    sparse — edge-parallel relaxation: ``cand = dist[src] + w`` scattered
             with min into ``dst`` — Bellman-Ford restricted to the
             improved frontier (sound for non-negative weights:
             un-improved sources cannot produce new improvements).
             ``use_frontier=False`` relaxes every edge every sweep (the
             level-synchronous baseline semantics; reference path only).
             Kernel path: the edge-parallel Pallas relax over CSR lane
             blocks (batched 2D state only) — *interpret mode only*: its
             dynamic gathers/scatters are interpret-validated and its
             whole-(S, n_pad)-state VMEM footprint is unbounded in
             ``n_pad``, so a compiled (real-TPU) kernel path dispatches
             the XLA sparse form instead, per the registry notes.

    Fact 1 generalizes: the new frontier is the improved set, and a sweep
    that improves nothing terminates.  Sweep count is bounded by the
    longest shortest path's hop count (Bellman-Ford depth).
    """
    def sparse_ref(f, d, p, step):
        # mask on the vertex side, then ONE lane gather: XLA's TPU
        # compiler miscompiles a batched scatter whose updates fuse two
        # lane gathers (wrong minima and sums on a v5e)
        fd = jnp.where(f != 0, d, INF) if use_frontier else d
        nd = d.at[..., dst_idx].min(fd[..., src_idx] + w_edges)
        new = nd < d
        return new.astype(jnp.int8), nd, p

    if use_kernel:
        assert use_frontier, "kernel path is frontier-gated by construction"
        ks = kernel_registry.get(TROPICAL)
        K = ks.forms
        # min finite edge weight — drives the kernel's settled-skip table
        # (padded lanes are +inf and fall out of the min)
        w_min = jnp.min(w_edges)

        dense = None
        if wdense is not None:
            def dense(f, d, p, step):
                fd = jnp.where(f != 0, d, INF)           # frontier rows only
                new, nd = K["dense"](fd, wdense, d, w_min,
                                     bs=min(f.shape[0], 128), bn=bn,
                                     bk=bk, interpret=interpret)
                return new, nd, p

        if not ks.dispatchable("sparse", interpret=interpret):
            sparse = sparse_ref    # compiled path: XLA scatter-min relax
        else:
            def sparse(f, d, p, step):
                new, nd = K["sparse"](f, d, src_idx, dst_idx, w_edges,
                                      eb=eb, interpret=interpret)
                return new, nd, p

        return dense, sparse

    dense = None
    if wdense is not None:
        def dense(f, d, p, step):
            fd = jnp.where(f != 0, d, INF)               # frontier rows only
            cand = minplus_candidates(fd, wdense, chunk=chunk)
            nd = jnp.minimum(d, cand)
            new = nd < d
            return new.astype(jnp.int8), nd, p

    return dense, sparse_ref


def minplus_candidates(fd: jax.Array, wdense: jax.Array, *,
                       chunk: int = 128) -> jax.Array:
    """The (min,+) matrix product ``cand[s, j] = min_k fd[s, k] + W[k, j]``
    — the GEMM-analogue behind the dense tropical form, factored out so
    the sharded executor can run it on a rectangular (K, N) row-block of
    the weight matrix (its k-partial sweeps).  ``chunk`` destination
    columns per ``lax.map`` step bound the (S, chunk, K) broadcast
    intermediate."""
    kdim, ndim = wdense.shape
    c = _pull_chunk_size(ndim, chunk)
    blocks = wdense.T.reshape(ndim // c, c, kdim)        # (nb, C, K) in-wts

    def one(block):                                      # (C, K)
        return jnp.min(fd[:, None, :] + block[None], axis=-1)

    cand = jnp.moveaxis(jax.lax.map(one, blocks), 0, 1)
    return cand.reshape(fd.shape[:-1] + (ndim,))


# --------------------------------------------------------------------------
# min-label semiring form (connected components)
# --------------------------------------------------------------------------

def minlabel_form(src_idx, dst_idx) -> SweepForm:
    """Min-label propagation sweep: ``labels[dst] ⊕= labels[src]`` with
    ⊕ = min.  Pass symmetrized edge arrays for *weakly* connected
    components.  The frontier is the changed-label set; Fact 1 is "no
    label lowered"."""
    def min_label(f, labels, p, step):
        nl = labels.at[..., dst_idx].min(labels[..., src_idx])
        changed = nl < labels
        return changed.astype(jnp.int8), nl, p
    return min_label


# --------------------------------------------------------------------------
# counting semiring forms (shortest-path counting — Brandes stage 1)
# --------------------------------------------------------------------------

def counting_forms(adj, src_idx, dst_idx, *, n_pad: int = 0, s: int = 0,
                   bn: int = 128, bk: int = 128,
                   use_kernel: bool = False,
                   interpret: bool = True) -> Tuple[SweepForm, SweepForm]:
    """(push, sparse) counting sweep forms.

    The loop state's ``dist`` slot is the PAIR ``(dist int32, sigma
    f32)``: ``dist`` is exactly the boolean semiring's level array and
    ``sigma[s, v]`` counts shortest s→v paths.  Because unweighted BFS is
    level-synchronous, *every* shortest path to a node first reached at
    this sweep enters through the current frontier, so one f32 matmul of
    frontier-masked sigma against the adjacency produces the complete
    count:

        cand[s, j] = Σ_k (frontier ? sigma : 0)[s, k] · A[k, j]
        new        = (cand > 0) & (dist == UNREACHED)
        dist'      = new ? step : dist          (the boolean update)
        sigma'     = new ? cand : sigma         (⊕ = add, gated on ties)

    ⊕ is elementwise ADD — non-idempotent, unlike OR/min — so partial
    candidates (sharded K-blocks, sparse scatter lanes) must be SUMMED
    exactly once per edge before the gate; the scatter-add form below and
    the sharded executor's masked-add reduction both preserve that.
    Counts are f32: exact up to 2^24 paths per (source, node) pair —
    beyond that the add rounds (documented in docs/ARCHITECTURE.md).

    ``adj`` is the dense int8 operand (a (1, 1) dummy when only sparse
    dispatches); ``use_kernel`` swaps the push closure for the fused
    counting Pallas kernel looked up in :mod:`repro.kernels.registry`.
    Settledness makes the boolean o_occ table sound here: sigma only
    changes where dist improves, so a tile with no unreached target
    cannot change either half of the state.
    """
    if use_kernel:
        K = kernel_registry.get(COUNTING).forms
        bs = min(s, 128) if s else 128

        def push(f, ds, p, step):
            d, sg = ds
            fs = jnp.where(f != 0, sg, 0.0)
            new, nd, nsg = K["push"](fs, adj, d, sg, step, bs=bs, bn=bn,
                                     bk=bk, interpret=interpret)
            return new, (nd, nsg), p
    else:
        def push(f, d_pair, p, step):
            d, sg = d_pair
            fs = jnp.where(f != 0, sg, 0.0)
            cand = jax.lax.dot_general(
                fs, adj.astype(jnp.float32),
                (((fs.ndim - 1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,   # exact f32 counts
                preferred_element_type=jnp.float32)
            new = (cand > 0) & (d == UNREACHED)
            return (new.astype(jnp.int8),
                    (jnp.where(new, step, d), jnp.where(new, cand, sg)), p)

    def sparse(f, d_pair, p, step):
        # edge-parallel scatter-ADD: each CSR lane contributes its source's
        # sigma once (lanes are deduped), so the sum over in-lanes is the
        # exact path count — the non-idempotent analogue of SOVM's
        # scatter-OR
        d, sg = d_pair
        fs = jnp.where(f != 0, sg, 0.0)     # one lane gather (see tropical)
        cand = jnp.zeros(d.shape, jnp.float32).at[..., dst_idx].add(
            fs[..., src_idx])
        new = (cand > 0) & (d == UNREACHED)
        return (new.astype(jnp.int8),
                (jnp.where(new, step, d), jnp.where(new, cand, sg)), p)

    return push, sparse


# --------------------------------------------------------------------------
# shortest-path tree post-pass
# --------------------------------------------------------------------------

def derive_parents(g, dist: jax.Array, *, weights=None) -> jax.Array:
    """Parent of v = any in-neighbor u on a shortest path (max u id wins —
    the same deterministic tie-break as the in-loop sparse tracking).

    Unweighted: ``dist[u] + 1 == dist[v]``.  Weighted (pass ``weights``):
    ``dist[u] + w(u, v) == dist[v]`` — exact because the sweeps computed
    dist[v] as that very f32 sum for at least one in-neighbor.

    dist is (..., n) over real nodes; one sparse pass over the padded CSR
    lanes, vmappable / jittable.
    """
    n = g.n_nodes
    pad = jnp.zeros(dist.shape[:-1] + (1,), dist.dtype)
    d = jnp.concatenate([dist, pad], axis=-1)           # sentinel column
    du, dv = d[..., g.src], d[..., g.dst]
    if weights is None:
        ok = (du != UNREACHED) & (dv == du + 1)
    else:
        w = jnp.where(g.src < n, weights, INF)
        ok = jnp.isfinite(du) & (dv == du + w)
    cand = jnp.where(ok, g.src, -1)
    par = jnp.full(d.shape, -1, jnp.int32).at[..., g.dst].max(cand)
    return par[..., :n]


# --------------------------------------------------------------------------
# wall-clock form calibration (the CPU-path direction signal)
# --------------------------------------------------------------------------

_CALIBRATION_SWEEPS = 8
_CALIBRATION_REPS = 5


def time_sweep_forms(forms: Sequence[SweepForm], frontier, dist,
                     parent: Optional[jax.Array] = None, *,
                     n_sweeps: int = _CALIBRATION_SWEEPS,
                     reps: int = _CALIBRATION_REPS) -> Tuple[float, ...]:
    """Median wall-clock seconds per sweep for each form on the given
    mid-BFS state.  Times a jitted block of ``n_sweeps`` chained sweeps so
    per-dispatch timer noise is drowned; the frontier must evolve or XLA
    hoists the loop-invariant sweep out of the fori_loop, so ``dist`` is
    refreshed every other sweep to keep the frontier alive.  Fixed-shape
    XLA sweeps cost the same at any occupancy, so one measurement
    characterizes every sweep of a run (see core/engine.py calibration).
    """
    if parent is None:
        parent = jnp.zeros((1,), jnp.int32)

    def chained(form):
        def go(fr, d, p):
            def body(i, c):
                new, dd, pp = form(c[0], c[1], c[2], i + 1)
                # dist may be a pytree (the counting semiring carries a
                # (dist, sigma) pair) — refresh every leaf
                refreshed = jax.tree_util.tree_map(
                    lambda orig, upd: jnp.where(i % 2 == 1, orig, upd),
                    d, dd)
                return (new, refreshed, pp)
            return jax.lax.fori_loop(0, n_sweeps, body, (fr, d, p))
        return jax.jit(go)

    costs = []
    for form in forms:
        fn = chained(form)
        jax.block_until_ready(fn(frontier, dist, parent))  # compile + warm
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(frontier, dist, parent))
            samples.append(time.perf_counter() - t0)
        costs.append(sorted(samples)[reps // 2] / n_sweeps)
    return tuple(costs)
