"""Pallas TPU kernels for the tropical (min,+) sweep — the weighted
engine's hot path (paper §5 grown onto the same substrate as BOVM).

``fused_minplus_sweep`` — dense direction (min-plus "GEMM" push).
  Grid (Si, Nj, Kk), K innermost, exactly the boolean ``fused_sweep``
  skeleton from ``kernels/common.py``: each (i, j) output tile
  accumulates ``min_k(fdist_block[s, k] + W_block[k, j])`` in a VMEM
  scratch (⊕ = min replaces the MXU add-accumulate; the inner min-plus
  runs one k lane per VPU step through ``common.lane_fold``, the same
  schedule as the packed kernels' word loop), then fuses the DAWN
  epilogue: improved-mask
  test, distance write.  Two scalar-prefetched occupancy tables gate
  every grid step:

    * f_occ[i, k] — frontier block (i, k) has any active source
                    (``isfinite`` of the frontier-masked distances);
    * o_occ[i, j] — output tile (i, j) has any *improvable* target.

  The boolean o_occ ("any unreached") is unsound for (min,+) — finite
  distances can still improve — so the tropical table generalizes
  Thm 3.2 through Dijkstra's settled criterion at tile rank:

    skip (i, j)  iff  dist[s, j'] <= min_k fdist[s, k] + w_min
                      for every (s, j') in the tile,

  where ``w_min`` is the graph's minimum edge weight.  Every candidate
  this sweep can produce for row s is >= min_fd[s] + w_min, so a tile of
  settled targets cannot improve: the skip is exact, not heuristic, and
  with unit weights it degenerates to the boolean "any unreached" table.

``sparse_relax_sweep`` — edge-parallel relaxation over CSR lanes.
  Grid (m_pad / eb,), sequential: each step gathers ``dist[:, src]``,
  adds the lane weights, masks to the frontier, and scatter-mins an
  (S, n_pad) VMEM accumulator (``eb`` edges relax in parallel per step);
  the last step fuses the epilogue.  Padded lanes carry the CSR sentinel
  (src = dst = n, w = +inf) and are inert.  Gather/scatter by edge index
  is validated under ``interpret=True`` (the CPU path this repo tests);
  on real TPU hardware prefer the dense kernel or the XLA sparse form —
  the registry notes record this caveat.

VMEM budgets (defaults): dense tiles (128×128 f32 fdist + 128×128 f32 W
+ 128×128 f32 dist/acc + i8+f32 out) ≈ 0.4 MB.  The sparse kernel keeps
whole (S, n_pad) state blocks resident (~14 B/entry: i8 frontier, f32
dist/acc/out, i8 out), so its footprint scales with S × n_pad — (64,
1152) ≈ 1.0 MB, but a 131k-node graph at S=64 would need ~117 MB: on
large graphs keep S small or prefer the dense kernel / XLA sparse form.
All dense dims are multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import common


# --------------------------------------------------------------------------
# dense direction: fused min-plus "GEMM" sweep
# --------------------------------------------------------------------------

def _minplus(acc: jax.Array, col: jax.Array, row: jax.Array) -> jax.Array:
    """One contraction lane of the (min, +) product: ``col`` (bs, 1)
    frontier distances plus ``row`` (1, bn) edge weights."""
    return jnp.minimum(acc, col + row)


def _minplus_sweep_kernel(f_occ_ref, o_occ_ref,        # scalar prefetch
                          fd_ref, w_ref, dist_ref,     # VMEM in
                          new_ref, dist_out_ref,       # VMEM out
                          acc_ref):                    # VMEM scratch f32
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)

    live = (f_occ_ref[i, k] > 0) & (o_occ_ref[i, j] > 0)

    @pl.when(live)
    def _accumulate():
        # fd (bs, bk) f32, +inf off-frontier; w (bk, bn) f32, +inf non-edge
        acc_ref[...] = common.lane_fold(fd_ref, w_ref, acc_ref[...],
                                        _minplus)

    @pl.when(k == nk - 1)
    def _epilogue():
        dist = dist_ref[...]
        cand = acc_ref[...]
        new = cand < dist
        new_ref[...] = new.astype(jnp.int8)
        dist_out_ref[...] = jnp.where(new, cand, dist)


@functools.partial(jax.jit, static_argnames=("bs", "bn", "bk", "interpret"))
def fused_minplus_sweep(fdist: jax.Array, wdense: jax.Array,
                        dist: jax.Array, w_min: jax.Array, *, bs: int = 128,
                        bn: int = 128, bk: int = 128,
                        interpret: bool = False):
    """One fused (min,+) sweep.  Shapes: fdist (S, k) f32 — the
    frontier-masked distances (``where(frontier, dist, +inf)``), wdense
    (k, n) f32 with +inf non-edges (square, k == n, on the single-device
    path; a K-row block, k = n/C, under the sharded executor — partials
    are min-combined across shards), dist (S, n) f32; ``w_min`` the
    scalar minimum finite edge weight (traced; drives the settled-skip
    table).  S % bs == 0, n % bn == 0, k % bk == 0.  Returns
    (new int8 (S, n), dist f32 (S, n)) — bit-identical to the dense
    reference form (f32 min is exact, the skips are provably inert)."""
    s, k = fdist.shape
    ka, n = wdense.shape
    assert ka == k and dist.shape == (s, n), \
        (fdist.shape, wdense.shape, dist.shape)
    common.check_push_tiles(s, n, bs, bn, bk, k=k)
    gi, gj, gk = s // bs, n // bn, k // bk

    f_occ = common.block_any(jnp.isfinite(fdist), gi, bs, gk, bk)
    # Dijkstra-style settled bound: row s cannot improve any target whose
    # distance is already <= min_k fdist[s, k] + w_min
    bound = jnp.min(fdist, axis=1, keepdims=True) + w_min    # (S, 1)
    o_occ = common.block_any(dist > bound, gi, bs, gj, bn)

    grid_spec = common.push_grid_spec(gi, gj, gk, bs=bs, bn=bn, bk=bk,
                                      num_scalar_prefetch=2,
                                      acc_dtype=jnp.float32)
    new, dist_out = pl.pallas_call(
        _minplus_sweep_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.float32)],
        compiler_params=common.sweep_compiler_params(),
        interpret=interpret,
    )(f_occ.astype(jnp.int32), o_occ.astype(jnp.int32), fdist, wdense, dist)
    return new, dist_out


# --------------------------------------------------------------------------
# fused multi-sweep persistent kernel (tropical): same skeleton as the
# boolean fused kernel — whole weight matrix resident, Fact 1 in-kernel
# --------------------------------------------------------------------------

def _fused_minplus_kernel(meta_ref,                        # scalar prefetch
                          f_ref, w_ref, dist_ref,          # VMEM in
                          new_ref, dist_out_ref,           # VMEM out
                          prod_ref, stop_ref,              # SMEM out (gi,)
                          fd_ref,                          # VMEM scratch
                          *, max_sweeps: int):
    n_run = meta_ref[1]                  # meta[0] (step) unused: dist is ⊕
    d0 = dist_ref[...]                   # (bs, n) f32; w (n, n) resident

    def sweep(t, carry):
        done, prod, f8, d, new8 = carry
        live = (done == 0) & (t < n_run)
        fd_ref[...] = jnp.where(f8 != 0, d, jnp.inf)
        cand = common.lane_fold(fd_ref, w_ref, jnp.full(d.shape, jnp.inf),
                                _minplus)
        new = cand < d
        any_new = jnp.any(new)
        d = jnp.where(new & live, cand, d)
        new8 = jnp.where(live, new.astype(jnp.int8), new8)
        f8 = jnp.where(live, new.astype(jnp.int8), f8)
        prod = prod + (live & any_new).astype(jnp.int32)
        done = done | (live & ~any_new).astype(jnp.int32)
        return done, prod, f8, d, new8

    done, prod, _, d, new8 = jax.lax.fori_loop(
        0, max_sweeps, sweep,
        (jnp.int32(0), jnp.int32(0), f_ref[...], d0,
         jnp.zeros(d0.shape, jnp.int8)))
    new_ref[...] = new8
    dist_out_ref[...] = d
    i = pl.program_id(0)
    prod_ref[i] = prod
    stop_ref[i] = done


@functools.partial(jax.jit,
                   static_argnames=("bs", "max_sweeps", "interpret"))
def fused_minplus_multisweep(frontier: jax.Array, wdense: jax.Array,
                             dist: jax.Array, step: jax.Array,
                             n_run: jax.Array, *, bs: int = 128,
                             max_sweeps: int = 1, interpret: bool = False):
    """Run up to ``n_run`` (min,+) sweeps in one invocation — the
    tropical instantiation of the fused multi-sweep skeleton (see the
    boolean ``fused_boolean_multisweep`` for the accounting contract).
    frontier (S, n) int8 improved-mask, wdense (n, n) f32 resident,
    dist (S, n) f32; ``step`` is accepted for signature uniformity but
    unused (tropical distances are the candidates themselves).  The
    per-lane min order matches the per-sweep kernel and reference forms,
    and f32 min is exact, so the fused block is bit-identical to
    ``n_run`` per-sweep dispatches.  Returns (new int8, dist f32,
    prod int32, stopped bool)."""
    del step
    s, n = frontier.shape
    assert wdense.shape == (n, n) and dist.shape == (s, n), \
        (frontier.shape, wdense.shape, dist.shape)
    assert s % bs == 0 and n % 128 == 0, (s, n, bs)
    gi = s // bs
    meta = jnp.stack([jnp.int32(0), jnp.asarray(n_run, jnp.int32)])

    grid_spec = common.fused_grid_spec(
        gi, bs=bs, n=n, f_block=(bs, n), op_block=(n, n),
        scratch_shapes=[pltpu.VMEM((bs, n), jnp.float32)])
    new, dist_out, prod, stop = pl.pallas_call(
        functools.partial(_fused_minplus_kernel, max_sweeps=max_sweeps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.float32),
                   jax.ShapeDtypeStruct((gi,), jnp.int32),
                   jax.ShapeDtypeStruct((gi,), jnp.int32)],
        compiler_params=common.fused_compiler_params(),
        interpret=interpret,
    )(meta, frontier, wdense, dist)
    return new, dist_out, jnp.max(prod), jnp.min(stop) > 0


# --------------------------------------------------------------------------
# sparse direction: edge-parallel relax over CSR lanes
# --------------------------------------------------------------------------

def _sparse_relax_kernel(f_ref, d_ref, src_ref, dst_ref, w_ref,  # VMEM in
                         new_ref, dist_out_ref,                  # VMEM out
                         acc_ref):                               # scratch f32
    k = pl.program_id(0)
    nk = pl.num_programs(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)

    src = src_ref[0, :]                       # (eb,) int32 lanes
    dst = dst_ref[0, :]
    w = w_ref[0, :]
    d = d_ref[...]                            # (S, n_pad) f32
    active = f_ref[...][:, src] != 0          # frontier gate per lane
    cand = jnp.where(active, d[:, src] + w[None, :], jnp.inf)
    acc_ref[...] = acc_ref[...].at[:, dst].min(cand)

    @pl.when(k == nk - 1)
    def _epilogue():
        new = acc_ref[...] < d
        new_ref[...] = new.astype(jnp.int8)
        dist_out_ref[...] = jnp.where(new, acc_ref[...], d)


@functools.partial(jax.jit, static_argnames=("eb", "interpret"))
def sparse_relax_sweep(frontier: jax.Array, dist: jax.Array,
                       src_idx: jax.Array, dst_idx: jax.Array,
                       w_edges: jax.Array, *, eb: int = 128,
                       interpret: bool = True):
    """One edge-parallel (min,+) relax sweep.  frontier (S, n_pad) int8,
    dist (S, n_pad) f32, src/dst (m_pad,) int32 CSR lanes (sentinel-
    padded), w_edges (m_pad,) f32 (+inf padded lanes).  m_pad % eb == 0
    (CSRGraph pads edges to multiples of 128).

    Interpret-only: the per-lane gathers/scatters are validated op-by-op,
    not under Mosaic compilation, and the whole-(S, n_pad)-state VMEM
    footprint is unbounded in n_pad — the registry marks the form
    ``interpret_only`` and ``sweep.tropical_forms`` dispatches the XLA
    scatter-min form instead on compiled backends.  This guard makes the
    contract a hard error rather than a registry convention."""
    if not interpret:
        raise RuntimeError(
            "sparse_relax_sweep is interpret-only (see the tropical "
            "KernelSet's interpret_only marker): compiled TPU dispatch "
            "must fall back to the XLA sparse form — "
            "sweep.tropical_forms does this automatically")
    s, n_pad = frontier.shape
    m_pad = src_idx.shape[0]
    assert dist.shape == (s, n_pad)
    assert dst_idx.shape == (m_pad,) and w_edges.shape == (m_pad,)
    assert m_pad % eb == 0, (m_pad, eb)
    gk = m_pad // eb
    # 2D (gk, eb) lane blocks: TPU block loads want >= 2D operands
    src2 = src_idx.reshape(gk, eb)
    dst2 = dst_idx.reshape(gk, eb)
    w2 = w_edges.reshape(gk, eb)

    full = lambda i: (0, 0)        # noqa: E731 — whole-state block per step
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(gk,),
        in_specs=[
            pl.BlockSpec((s, n_pad), full),
            pl.BlockSpec((s, n_pad), full),
            pl.BlockSpec((1, eb), lambda i: (i, 0)),
            pl.BlockSpec((1, eb), lambda i: (i, 0)),
            pl.BlockSpec((1, eb), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((s, n_pad), full),
            pl.BlockSpec((s, n_pad), full),
        ],
        scratch_shapes=[pltpu.VMEM((s, n_pad), jnp.float32)],
    )
    new, dist_out = pl.pallas_call(
        _sparse_relax_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n_pad), jnp.int8),
                   jax.ShapeDtypeStruct((s, n_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(frontier, dist, src2, dst2, w2)
    return new, dist_out
