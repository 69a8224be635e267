"""Pallas TPU kernels for the DAWN sweep (the paper's compute hot spot).

Four kernels: both paper directions, each bit-packed, plus the f32 GEMM
push and the fused multi-sweep persistent kernel:

``packed_push_kernel`` — push direction, bit-packed (the engine default).
  The boolean push and pull sweeps are the SAME computation once the
  frontier is packed over the contraction axis:
  hits[s, j] = OR_w(frontier[s, w] & in_nbrs[j, w]) — so the push form
  drives the identical word-AND/OR math over ``adj_pull`` with a 128-row
  source tile and the push kernel's occupancy gating (f_occ frontier
  blocks, o_occ unreached tiles — Thm 3.2 at tile rank).  This is the
  paper's Eq. 13 BOVM memory model made compute: 32 frontier lanes per
  uint32 op, no f32 GEMM anywhere on the boolean kernel path.

``fused_boolean_kernel`` — the fused multi-sweep persistent kernel.
  Grid (S/bs,) over source tiles only; each invocation runs up to
  ``max_sweeps`` sweeps with the packed frontier, distances and the whole
  packed operand resident in VMEM, evaluating the Fact-1 convergence
  check in-kernel.  Source tiles evolve independently (the operand is
  read-only), and a tile's productivity is prefix-contiguous (an empty
  frontier stays empty), so per-tile (productive-count, converged) pairs
  max/all-reduce to exactly the per-sweep loop's global accounting — the
  wrapper returns them and ``core/sweep.py::sweep_loop`` advances its
  step/sweeps counters as if each sweep had been dispatched separately.

``fused_sweep_kernel`` — push direction (paper Alg. 1 as batched GEMM).
  Grid (Si, Nj, Kk), K innermost.  Each (i, j) output tile accumulates
  frontier-block × adjacency-block products on the MXU, then fuses the
  DAWN epilogue (hit test + Thm 3.2 visited-skip + distance write).
  The paper's per-element early exit becomes tile skipping driven by two
  scalar-prefetched occupancy tables:
    * f_occ[i, k]  — frontier block (i, k) has any active source
                     (input sparsity: late sweeps have tiny frontiers);
    * o_occ[i, j]  — output tile (i, j) has any unreached target
                     (output sparsity: early tiles retire as distances fill —
                     exactly Thm 3.2 "skip discovered targets" at tile rank).
  A skipped (i, j, k) step performs no MXU work and no VMEM traffic beyond
  the (already scheduled) block fetches.

``packed_pull_kernel`` — pull direction (paper's CSC BOVM, §3.2), bit-packed.
  hits[s, j] = OR_w(frontier[s, w] & in_nbrs[j, w]) over uint32 words:
  32 nodes/byte-lane, pure VPU bitwise ops — the TPU analogue of the
  boolean-compression argument in Eq. 3/4.

VMEM budgets (defaults): push tiles (128×512 f + 512×128 a + 128×128 acc/out)
≈ 0.6 MB;  pull tiles (128×W_blk + 128×W_blk uint32 + 128×128 acc) ≲ 1 MB.
All matmul dims are multiples of 128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import common


# --------------------------------------------------------------------------
# push direction: fused masked GEMM sweep
# --------------------------------------------------------------------------

def _fused_sweep_kernel(f_occ_ref, o_occ_ref, step_ref,        # scalar prefetch
                        f_ref, a_ref, dist_ref,                # VMEM in
                        new_ref, dist_out_ref,                 # VMEM out
                        acc_ref):                              # VMEM scratch
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (f_occ_ref[i, k] > 0) & (o_occ_ref[i, j] > 0)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += jnp.dot(
            f_ref[...].astype(jnp.float32), a_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        dist = dist_ref[...]
        new = (acc_ref[...] > 0) & (dist < 0)
        new_ref[...] = new.astype(jnp.int8)
        dist_out_ref[...] = jnp.where(new, step_ref[0], dist)


@functools.partial(jax.jit, static_argnames=("bs", "bn", "bk", "interpret"))
def fused_sweep(frontier: jax.Array, adj: jax.Array, dist: jax.Array,
                step: jax.Array, *, bs: int = 128, bn: int = 128,
                bk: int = 512, interpret: bool = False):
    """One fused DAWN sweep. Shapes: frontier (S,k) int8, adj (k,n) int8,
    dist (S,n) int32; S % bs == 0, n % bn == 0, k % bk == 0.  The square
    single-device operand has k == n; the sharded executor dispatches a
    K-row block (k = n/C) and OR-combines the partial across shards."""
    s, k = frontier.shape
    ka, n = adj.shape
    assert ka == k and dist.shape == (s, n), \
        (frontier.shape, adj.shape, dist.shape)
    common.check_push_tiles(s, n, bs, bn, bk, k=k)
    gi, gj, gk = s // bs, n // bn, k // bk

    # occupancy tables (computed by XLA; cheap VPU reproductions per sweep)
    f_occ = common.block_any(frontier != 0, gi, bs, gk, bk)
    o_occ = common.block_any(dist < 0, gi, bs, gj, bn)
    step_arr = jnp.asarray(step, jnp.int32).reshape(1)

    grid_spec = common.push_grid_spec(gi, gj, gk, bs=bs, bn=bn, bk=bk,
                                      num_scalar_prefetch=3,
                                      acc_dtype=jnp.float32)
    new, dist_out = pl.pallas_call(
        _fused_sweep_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.int32)],
        compiler_params=common.sweep_compiler_params(),
        interpret=interpret,
    )(f_occ.astype(jnp.int32), o_occ.astype(jnp.int32), step_arr,
      frontier, adj, dist)
    return new, dist_out


# --------------------------------------------------------------------------
# pull direction: bit-packed AND/OR sweep (VPU)
# --------------------------------------------------------------------------

def _packed_pull_kernel(step_ref,                 # scalar prefetch
                        f_ref, at_ref, dist_ref,  # VMEM in
                        new_ref, dist_out_ref,    # VMEM out
                        acc_ref, at_t_ref):       # VMEM scratch
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] = _word_hits(f_ref, at_ref, at_t_ref, acc_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        dist = dist_ref[...]
        new = (acc_ref[...] > 0) & (dist < 0)
        new_ref[...] = new.astype(jnp.int8)
        dist_out_ref[...] = jnp.where(new, step_ref[0], dist)


def _or_hit(acc: jax.Array, fw: jax.Array, aw: jax.Array) -> jax.Array:
    """One word of the (∨, ∧) product: ``fw`` (bs, 1) frontier words
    against ``aw`` (1, bn) in-neighbour words — 32 contraction lanes."""
    return acc | ((fw & aw) != 0).astype(jnp.int32)


def _word_hits(f_ref, at_ref, at_t_ref, acc: jax.Array) -> jax.Array:
    """OR over packed words: acc[s, j] |= any_w(f[s, w] & at[j, w]).
    ``f_ref`` (bs, wk) uint32, ``at_ref`` (bn, wk) uint32, ``acc`` (bs,
    bn) int32 — the VPU inner loop shared by the packed pull AND packed
    push kernels.  The operand block is transposed once per grid step
    into ``at_t_ref`` (wk, bn) so each word is a row (a sublane load)
    that broadcasts against a frontier column."""
    at_t_ref[...] = at_ref[...].T
    return common.lane_fold(f_ref, at_t_ref, acc, _or_hit)


@functools.partial(jax.jit, static_argnames=("bs", "bn", "wk", "interpret"))
def packed_pull_sweep(frontier_packed: jax.Array, adj_in_packed: jax.Array,
                      dist: jax.Array, step: jax.Array, *, bs: int = 8,
                      bn: int = 128, wk: int = 128, interpret: bool = False):
    """Bit-packed pull sweep.  frontier_packed (S, W) uint32,
    adj_in_packed (n, W) uint32 (row j = packed in-neighbours of j),
    dist (S, n) int32.  S % bs == 0, n % bn == 0, W % wk == 0, and the
    word tile ``wk`` is a multiple of 128 or the whole W (a block's last
    dim — ``common.word_tile`` picks it)."""
    s, w = frontier_packed.shape
    n = adj_in_packed.shape[0]
    assert adj_in_packed.shape == (n, w) and dist.shape == (s, n)
    assert s % bs == 0 and n % bn == 0 and w % wk == 0, (s, n, w, bs, bn, wk)
    gi, gj, gk = s // bs, n // bn, w // wk
    step_arr = jnp.asarray(step, jnp.int32).reshape(1)

    grid_spec = common.pull_grid_spec(gi, gj, gk, bs=bs, bn=bn, wk=wk,
                                      num_scalar_prefetch=1,
                                      acc_dtype=jnp.int32,
                                      extra_scratch=[
                                          pltpu.VMEM((wk, bn), jnp.uint32)])
    new, dist_out = pl.pallas_call(
        _packed_pull_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.int32)],
        compiler_params=common.sweep_compiler_params(),
        interpret=interpret,
    )(step_arr, frontier_packed, adj_in_packed, dist)
    return new, dist_out


# --------------------------------------------------------------------------
# push direction, bit-packed: the same word math as pull, with the push
# kernel's occupancy gating — the engine's boolean kernel default
# --------------------------------------------------------------------------

def _packed_push_kernel(f_occ_ref, o_occ_ref, step_ref,   # scalar prefetch
                        f_ref, at_ref, dist_ref,          # VMEM in
                        new_ref, dist_out_ref,            # VMEM out
                        acc_ref, at_t_ref):               # VMEM scratch
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (f_occ_ref[i, k] > 0) & (o_occ_ref[i, j] > 0)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] = _word_hits(f_ref, at_ref, at_t_ref, acc_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        dist = dist_ref[...]
        new = (acc_ref[...] > 0) & (dist < 0)
        new_ref[...] = new.astype(jnp.int8)
        dist_out_ref[...] = jnp.where(new, step_ref[0], dist)


@functools.partial(jax.jit, static_argnames=("bs", "bn", "wk", "interpret"))
def packed_push_sweep(frontier_packed: jax.Array, adj_in_packed: jax.Array,
                      dist: jax.Array, step: jax.Array, *, bs: int = 128,
                      bn: int = 128, wk: int = 128, interpret: bool = False):
    """Bit-packed push sweep.  frontier_packed (S, W) uint32 — the packed
    frontier over the contraction axis — adj_in_packed (n, W) uint32 (the
    same operand the pull kernel reads; for a sharded K-row block the W
    words cover the block's k rows), dist (S, n) int32.  S % bs == 0,
    n % bn == 0, W % wk == 0 (``wk`` a multiple of 128 or the whole W).
    Emits NO f32 GEMM: the (∨, ∧) product is pure uint32 word AND/OR on
    the VPU (paper Eq. 13: 32 lanes/word), gated by the push kernel's
    f_occ/o_occ occupancy tables."""
    s, w = frontier_packed.shape
    n = adj_in_packed.shape[0]
    assert adj_in_packed.shape == (n, w) and dist.shape == (s, n)
    assert s % bs == 0 and n % bn == 0 and w % wk == 0, (s, n, w, bs, bn, wk)
    gi, gj, gk = s // bs, n // bn, w // wk

    f_occ = common.block_any(frontier_packed != 0, gi, bs, gk, wk)
    o_occ = common.block_any(dist < 0, gi, bs, gj, bn)
    step_arr = jnp.asarray(step, jnp.int32).reshape(1)

    grid_spec = common.pull_grid_spec(gi, gj, gk, bs=bs, bn=bn, wk=wk,
                                      num_scalar_prefetch=3,
                                      acc_dtype=jnp.int32,
                                      extra_scratch=[
                                          pltpu.VMEM((wk, bn), jnp.uint32)])
    new, dist_out = pl.pallas_call(
        _packed_push_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.int32)],
        compiler_params=common.sweep_compiler_params(),
        interpret=interpret,
    )(f_occ.astype(jnp.int32), o_occ.astype(jnp.int32), step_arr,
      frontier_packed, adj_in_packed, dist)
    return new, dist_out


# --------------------------------------------------------------------------
# fused multi-sweep persistent kernel (boolean): K sweeps — or the whole
# fixpoint — per invocation, Fact 1 evaluated in-kernel
# --------------------------------------------------------------------------

def _group_words(mask: jax.Array) -> jax.Array:
    """(bs, n) bool -> (bs, n) uint32 whose lane 32·w holds packed word
    w — bit b = ``mask[:, 32w + b]``, the little-endian layout of
    ``core.frontier.pack_bits``.  The in-kernel re-pack of the new
    frontier between fused sweeps: each lane gets its bit at position
    ``lane % 32``, then five lane rotations OR lanes l .. l+31 into lane
    l.  Only lanes 32·w are read (``lane_fold(stride=32)``), and for
    them the window never wraps past n, so no compaction (a lane-split
    reshape Mosaic cannot lower) is needed."""
    n = mask.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1)
    v = mask.astype(jnp.uint32) << (lane & 31).astype(jnp.uint32)
    for sh in (1, 2, 4, 8, 16):
        v = v | pltpu.roll(v, n - sh, 1)      # v[l] |= v[l + sh]
    return v


def _fused_boolean_kernel(meta_ref,                        # scalar prefetch
                          f_ref, at_ref, dist_ref,         # VMEM in
                          new_ref, dist_out_ref,           # VMEM out
                          prod_ref, stop_ref,              # SMEM out (gi,)
                          at_t_ref, words_ref,             # VMEM scratch
                          *, max_sweeps: int):
    step0 = meta_ref[0]
    n_run = meta_ref[1]
    at_t_ref[...] = at_ref[...].T        # (W, n) uint32, resident throughout
    d0 = dist_ref[...]                   # (bs, n) int32
    words_ref[...] = _group_words(f_ref[...] != 0)

    def sweep(t, carry):
        done, prod, d, new8 = carry
        live = (done == 0) & (t < n_run)
        hits = common.lane_fold(words_ref, at_t_ref,
                                jnp.zeros(d.shape, jnp.int32), _or_hit,
                                stride=32)
        new = (hits > 0) & (d < 0)
        any_new = jnp.any(new)
        d = jnp.where(new & live, step0 + 1 + t, d)
        new8 = jnp.where(live, new.astype(jnp.int8), new8)
        words_ref[...] = jnp.where(live, _group_words(new), words_ref[...])
        prod = prod + (live & any_new).astype(jnp.int32)
        done = done | (live & ~any_new).astype(jnp.int32)
        return done, prod, d, new8

    done, prod, d, new8 = jax.lax.fori_loop(
        0, max_sweeps, sweep,
        (jnp.int32(0), jnp.int32(0), d0, jnp.zeros(d0.shape, jnp.int8)))
    new_ref[...] = new8
    dist_out_ref[...] = d
    i = pl.program_id(0)
    prod_ref[i] = prod
    stop_ref[i] = done


@functools.partial(jax.jit,
                   static_argnames=("bs", "max_sweeps", "interpret"))
def fused_boolean_multisweep(frontier: jax.Array, adj_in_packed: jax.Array,
                             dist: jax.Array, step: jax.Array,
                             n_run: jax.Array, *, bs: int = 128,
                             max_sweeps: int = 1, interpret: bool = False):
    """Run up to ``n_run`` boolean sweeps (``n_run <= max_sweeps``, the
    static unroll bound) in ONE kernel invocation.  frontier (S, n) int8
    (packed in VMEM before every sweep), adj_in_packed
    (n, W) uint32 fully resident, dist (S, n) int32, ``step`` the sweeps
    already executed (sweep t writes distance step + 1 + t).

    Each source tile runs its own Fact-1 check in-kernel: a tile whose
    sweep settles nothing zeroes its frontier and holds state for the
    rest of the block.  Returns (new int8, dist int32, prod int32 scalar,
    stopped bool scalar) where ``prod = max over tiles`` of productive
    sweeps and ``stopped = all tiles converged`` — because per-tile
    productivity is prefix-contiguous, the per-sweep driver's global
    accounting is ``executed = stopped ? prod + 1 : n_run`` exactly (see
    ``sweep_loop``'s fused body).  Bit-identical to ``n_run`` dispatches
    of the per-sweep path."""
    s, n = frontier.shape
    w = adj_in_packed.shape[1]
    assert adj_in_packed.shape == (n, w), (adj_in_packed.shape, n)
    assert dist.shape == (s, n) and w * 32 == n, (frontier.shape, w)
    assert s % bs == 0 and n % 128 == 0, (s, n, bs)
    gi = s // bs

    meta = jnp.stack([jnp.asarray(step, jnp.int32),
                      jnp.asarray(n_run, jnp.int32)])

    grid_spec = common.fused_grid_spec(
        gi, bs=bs, n=n, f_block=(bs, n), op_block=(n, w),
        scratch_shapes=[pltpu.VMEM((w, n), jnp.uint32),
                        pltpu.VMEM((bs, n), jnp.uint32)])
    new, dist_out, prod, stop = pl.pallas_call(
        functools.partial(_fused_boolean_kernel, max_sweeps=max_sweeps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.int32),
                   jax.ShapeDtypeStruct((gi,), jnp.int32),
                   jax.ShapeDtypeStruct((gi,), jnp.int32)],
        compiler_params=common.fused_compiler_params(),
        interpret=interpret,
    )(meta, frontier, adj_in_packed, dist)
    return new, dist_out, jnp.max(prod), jnp.min(stop) > 0
