"""Shared tiling / occupancy / grid machinery for every semiring kernel.

One substrate, N semirings: the boolean push/pull kernels
(``kernels/bovm``), the tropical min-plus kernels
(``kernels/tropical``) and the counting-semiring kernel
(``kernels/counting`` — two state arrays through the same grid) are
instantiations of the same skeleton —

  * a ``(S/bs, n/bn, n/bk)`` grid with K innermost ("arbitrary") so each
    output tile accumulates operand-block products in a VMEM scratch and
    fuses the DAWN epilogue on the last K step;
  * scalar-prefetched occupancy tables (``f_occ`` input sparsity,
    ``o_occ`` output sparsity — Thm 3.2 at tile rank) that gate each grid
    step before any VMEM compute;
  * MXU-aligned tile sizes validated against the per-core VMEM budget.

This module owns the pieces the semirings share: interpret-mode backend
detection, the blockwise ``any`` reduction behind both occupancy tables,
the lane fold behind every VPU contraction, the push/pull grid-spec
builders, the word-tile rule, and the VMEM budget math quoted in
docs/ARCHITECTURE.md.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MXU_ALIGN = 128                      # matmul dims must be multiples of this
LANES = 128                          # a block's last dim: multiple, or whole
# default per-core budget when no TuningPlan overrides it (the historical
# hard-coded table value; core/autotune.py BackendProfile carries the
# per-device figure and threads it through vmem_limit())
VMEM_BUDGET_BYTES = 16 * 2 ** 20

# tile-edge candidates the autotuner searches, largest first (all
# MXU-aligned; 128 is always a candidate so every padded n divides one)
TILE_CANDIDATES = (512, 256, 128)


def vmem_limit(budget: int | None = None) -> int:
    """The per-core VMEM byte budget tile plans must fit: ``budget``
    when a BackendProfile/TuningPlan supplies one, else the static
    default."""
    return VMEM_BUDGET_BYTES if budget is None else int(budget)


def tile_candidates(n_pad: int) -> tuple[int, ...]:
    """MXU-aligned tile edges that divide ``n_pad``, largest first."""
    cands = tuple(c for c in TILE_CANDIDATES
                  if c <= n_pad and n_pad % c == 0)
    return cands or (MXU_ALIGN,)


# scoped-VMEM ceiling for the fused kernels, whose whole operand, its
# transposed copy and (bs, n) state values all live for the sweep block
# (v5e has 128 MiB of VMEM per core; the default scoped limit is 16 MiB)
FUSED_VMEM_LIMIT_BYTES = 96 * 2 ** 20


def default_interpret() -> bool:
    """Pallas kernels execute op-by-op (interpret mode) off-TPU."""
    return jax.default_backend() != "tpu"


def word_tile(words: int) -> int:
    """Word (contraction) tile of the bit-packed kernels: a block's last
    dim must be a multiple of 128 lanes or the whole array width, so
    take 128 words when they divide ``words`` and the whole width
    otherwise (``n_pad`` is only 128-aligned, so ``n_pad / 32`` often is
    not)."""
    return LANES if words % LANES == 0 else words


def sweep_compiler_params():
    """The shared grid semantics: (i, j) output tiles are parallel, the
    K reduction axis is sequential (scratch accumulator carries state)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def fused_compiler_params():
    """Fused multi-sweep grids iterate source tiles only; each tile runs
    its whole sweep block to convergence, so the single axis is
    "arbitrary" (tiles are independent but internally stateful)."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=FUSED_VMEM_LIMIT_BYTES)


# --------------------------------------------------------------------------
# the VPU contraction shared by the bit-packed and min-plus kernels
# --------------------------------------------------------------------------

def lane_fold(x_ref, y_ref, acc: jax.Array, step, *,
              stride: int = 1) -> jax.Array:
    """Fold ``step`` over the K contraction steps of ``x_ref`` (rows,
    K·stride) — K on lanes — and ``y_ref`` (K, cols) — K on sublanes:

        acc = step(acc, x[:, k·stride : k·stride+1], y[k:k+1, :])

    for k = 0 .. K-1: a semiring outer-product accumulation, one (rows,
    1) column of x against one (1, cols) row of y per step.  Mosaic has
    no dynamic slice along lanes, so x is walked in 128-lane chunks (a
    ``fori_loop`` of aligned dynamic loads), each chunk unrolled with
    static lane slices; y rows are dynamic sublane loads.  A K that does
    not fill whole chunks ends in a static tail.  ``stride`` > 1 reads
    every stride-th lane of x (the fused boolean kernel keeps word w at
    lane 32·w).  The fold is k-ascending, though the idempotent ⊕'s
    (OR, min) do not need any order for bit-identity."""
    k_total = y_ref.shape[0]
    per_chunk = LANES // stride

    def fold(acc, xc, row0, width):
        for t in range(width):
            acc = step(acc, xc[:, t * stride:t * stride + 1],
                       y_ref[pl.ds(row0 + t, 1), :])
        return acc

    n_full = k_total // per_chunk
    if n_full:
        def chunk(c, acc):
            base = pl.multiple_of(c * LANES, LANES)
            return fold(acc, x_ref[:, pl.ds(base, LANES)], c * per_chunk,
                        per_chunk)

        acc = jax.lax.fori_loop(0, n_full, chunk, acc)
    lo = n_full * per_chunk
    if lo < k_total:
        acc = fold(acc, x_ref[:, lo * stride:], lo, k_total - lo)
    return acc


# --------------------------------------------------------------------------
# occupancy tables (the Thm 3.2 tile-skip signals, semiring-generic)
# --------------------------------------------------------------------------

def block_any(mask: jax.Array, gi: int, bi: int, gj: int, bj: int
              ) -> jax.Array:
    """(gi*bi, gj*bj) bool -> (gi, gj) bool: does block (i, j) contain any
    True?  This one reduction is both occupancy tables:

      f_occ = block_any(frontier-active mask, gi, bs, gk, bk)
      o_occ = block_any(semiring's improvable mask, gi, bs, gj, bn)

    where "improvable" is ``dist == UNREACHED`` for the boolean semiring
    (settled distances never change) and the settled-bound test
    ``dist > min_frontier_dist + w_min`` for the tropical semiring (see
    kernels/tropical/kernel.py for the soundness argument).
    """
    return jnp.any(mask.reshape(gi, bi, gj, bj), axis=(1, 3))


def check_push_tiles(s: int, n: int, bs: int, bn: int, bk: int,
                     k: int | None = None) -> None:
    """Tile divisibility contract shared by the push-style kernels.
    ``k`` is the contraction dim — it equals ``n`` for the square
    single-device operands and ``n/C`` for a sharded K-row block."""
    k = n if k is None else k
    assert s % bs == 0 and n % bn == 0 and k % bk == 0, (s, n, k, bs, bn, bk)


# --------------------------------------------------------------------------
# grid specs (one (i, j, k) skeleton, two operand layouts)
# --------------------------------------------------------------------------

def push_grid_spec(gi: int, gj: int, gk: int, *, bs: int, bn: int, bk: int,
                   num_scalar_prefetch: int, acc_dtype,
                   n_state: int = 1) -> "pltpu.PrefetchScalarGridSpec":
    """Grid spec for push-direction sweeps (boolean GEMM, tropical
    min-plus "GEMM", counting f32 GEMM): frontier-state block (i, k),
    operand block (k, j), ``n_state`` per-(i, j) state tiles in and
    ``n_state + 1`` tiles out (the improved-mask plus each updated state
    array), one (bs, bn) scratch accumulator.  The boolean/tropical
    kernels carry one state array (dist); the counting kernel carries two
    (dist + sigma, ``n_state=2``)."""
    state_spec = pl.BlockSpec((bs, bn), lambda i, j, k, *_: (i, j))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=(gi, gj, gk),
        in_specs=[
            pl.BlockSpec((bs, bk), lambda i, j, k, *_: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j)),
        ] + [state_spec] * n_state,
        out_specs=[state_spec] * (n_state + 1),
        scratch_shapes=[pltpu.VMEM((bs, bn), acc_dtype)],
    )


def pull_grid_spec(gi: int, gj: int, gk: int, *, bs: int, bn: int, wk: int,
                   num_scalar_prefetch: int, acc_dtype,
                   extra_scratch=()) -> "pltpu.PrefetchScalarGridSpec":
    """Grid spec for pull-direction sweeps (bit-packed boolean): packed
    frontier block (i, k), packed in-neighbour block (j, k), the (bs, bn)
    accumulator plus any ``extra_scratch`` (the transposed word block)."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=(gi, gj, gk),
        in_specs=[
            pl.BlockSpec((bs, wk), lambda i, j, k, *_: (i, k)),
            pl.BlockSpec((bn, wk), lambda i, j, k, *_: (j, k)),
            pl.BlockSpec((bs, bn), lambda i, j, k, *_: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bs, bn), lambda i, j, k, *_: (i, j)),
            pl.BlockSpec((bs, bn), lambda i, j, k, *_: (i, j)),
        ],
        scratch_shapes=[pltpu.VMEM((bs, bn), acc_dtype)] + list(extra_scratch),
    )


def fused_grid_spec(gi: int, *, bs: int, n: int, f_block, op_block,
                    num_scalar_prefetch: int = 1, n_state: int = 1,
                    scratch_shapes=()) -> "pltpu.PrefetchScalarGridSpec":
    """Grid spec for the fused multi-sweep (persistent) kernels: grid
    ``(gi,)`` over source tiles only — each grid step keeps its frontier
    block ``f_block`` at ``(i, 0)``, the *whole* operand ``op_block`` at
    ``(0, 0)``, and ``n_state`` per-row state tiles ``(bs, n)`` resident
    in VMEM while it runs up to ``max_sweeps`` sweeps internally (the
    Fact-1 check fires in-kernel).  Outputs: the last sweep's improved
    mask, the updated state arrays, and two ``(gi,)`` int32 per-tile
    scalars in SMEM — the productive-sweep count and the converged flag,
    written at ``[program_id(0)]`` — that the wrapper max/all-reduces
    into the loop driver's accounting."""
    state_spec = pl.BlockSpec((bs, n), lambda i, *_: (i, 0))
    flag_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch,
        grid=(gi,),
        in_specs=[
            pl.BlockSpec(f_block, lambda i, *_: (i, 0)),
            pl.BlockSpec(op_block, lambda i, *_: (0, 0)),
        ] + [state_spec] * n_state,
        out_specs=[state_spec] * (n_state + 1) + [flag_spec, flag_spec],
        scratch_shapes=list(scratch_shapes),
    )


# --------------------------------------------------------------------------
# VMEM budget math (the numbers in docs/ARCHITECTURE.md)
# --------------------------------------------------------------------------

def push_vmem_bytes(bs: int, bn: int, bk: int, *, f_itemsize: int,
                    a_itemsize: int, d_itemsize: int, acc_itemsize: int,
                    out_itemsizes: Sequence[int]) -> int:
    """Resident VMEM for one push-style grid step: frontier-state tile
    (bs, bk) + operand tile (bk, bn) + dist tile + scratch + outputs."""
    return (bs * bk * f_itemsize + bk * bn * a_itemsize
            + bs * bn * (d_itemsize + acc_itemsize + sum(out_itemsizes)))


def pull_vmem_bytes(bs: int, bn: int, wk: int, *, word_itemsize: int,
                    d_itemsize: int, acc_itemsize: int,
                    out_itemsizes: Sequence[int]) -> int:
    """Resident VMEM for one pull-style grid step: frontier and operand
    word blocks plus the operand block's (wk, bn) transposed copy."""
    return ((bs + 2 * bn) * wk * word_itemsize
            + bs * bn * (d_itemsize + acc_itemsize + sum(out_itemsizes)))


def fused_vmem_bytes(*, bs: int, n: int, operand_bytes: int,
                     frontier_bytes: int, state_itemsizes: Sequence[int],
                     out_itemsizes: Sequence[int]) -> int:
    """Resident VMEM for one fused multi-sweep grid step: the WHOLE
    operand plus the tile's frontier block, state arrays (in + carried)
    and outputs all live for the entire sweep block — the residency the
    fused path trades for its dispatch amortization (unlike the per-sweep
    grids, footprint scales with n² through ``operand_bytes``).  The two
    (1, 1) accounting scalars round up to 16 bytes."""
    return (operand_bytes + frontier_bytes
            + bs * n * (sum(state_itemsizes) + sum(out_itemsizes)) + 16)
