"""Pallas TPU kernel for the counting-semiring sweep (Brandes stage 1 —
shortest-path counting on the BOVM substrate).

``fused_counting_sweep`` — push direction on the shared skeleton from
``kernels/common.py``: grid (Si, Nj, Kk), K innermost, each (i, j) output
tile accumulating ``fsigma_block @ adj_block`` f32 MXU products in a VMEM
scratch, then fusing the counting epilogue on the last K step:

    new    = (acc > 0) & (dist < 0)        (the boolean discovery test)
    dist'  = new ? step : dist
    sigma' = new ? acc  : sigma            (⊕ = add, gated on dist ties)

The input frontier operand is ``fsigma = where(frontier, sigma, 0)`` —
the frontier-masked path counts — so the very matmul that detects
discovery (acc > 0 is exactly "any frontier in-neighbour") also sums the
shortest-path counts over all of them: one MXU pass produces both halves
of the (dist, sigma) state.

Tile skipping: ``f_occ[i, k]`` gates on any nonzero fsigma lane (counts
are strictly positive on the frontier); the boolean ``o_occ[i, j]`` "any
unreached target" table is SOUND for this semiring even though ⊕ = add
is not idempotent — sigma only ever changes where dist improves, and
dist only improves on unreached targets, so a tile with no unreached
target can change neither array.  (Contrast the tropical kernel, which
needs the settled-bound generalization.)

Like the boolean/tropical push kernels the operand may be a rectangular
(k = n/C) K-row block under the sharded executor; partial candidates are
then psum-combined across shards *before* the gate (masked-add ⊕ — see
core/distributed.py), because add-of-epilogue-outputs would double-gate.

VMEM (defaults bs=bn=bk=128): f32 fsigma + i8 adj + i32 dist + f32
sigma/acc + (i8, i32, f32) outputs ≈ 0.4 MB — see the table in
docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import common

# path counts need all 24 bits of the f32 mantissa: the MXU's default
# f32 precision rounds the operands to bfloat16 (8 bits) first
EXACT = jax.lax.Precision.HIGHEST


def _counting_sweep_kernel(f_occ_ref, o_occ_ref, step_ref,   # scalar prefetch
                           fs_ref, a_ref, dist_ref, sig_ref,  # VMEM in
                           new_ref, dist_out_ref, sig_out_ref,  # VMEM out
                           acc_ref):                          # VMEM scratch
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (f_occ_ref[i, k] > 0) & (o_occ_ref[i, j] > 0)

    @pl.when(live)
    def _accumulate():
        acc_ref[...] += jnp.dot(
            fs_ref[...], a_ref[...].astype(jnp.float32),
            precision=EXACT, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        dist = dist_ref[...]
        cand = acc_ref[...]
        new = (cand > 0) & (dist < 0)
        new_ref[...] = new.astype(jnp.int8)
        dist_out_ref[...] = jnp.where(new, step_ref[0], dist)
        sig_out_ref[...] = jnp.where(new, cand, sig_ref[...])


@functools.partial(jax.jit, static_argnames=("bs", "bn", "bk", "interpret"))
def fused_counting_sweep(fsigma: jax.Array, adj: jax.Array, dist: jax.Array,
                         sigma: jax.Array, step: jax.Array, *, bs: int = 128,
                         bn: int = 128, bk: int = 128,
                         interpret: bool = False):
    """One fused counting sweep.  Shapes: fsigma (S, k) f32 — the
    frontier-masked path counts (``where(frontier, sigma, 0)``), adj
    (k, n) int8 (square k == n single-device; a K-row block k = n/C under
    the sharded executor — partials are masked-add-combined across
    shards), dist (S, n) int32, sigma (S, n) f32.  S % bs == 0,
    n % bn == 0, k % bk == 0.  Returns (new int8, dist int32, sigma f32)
    — bit-identical to the reference form (f32 sums commute per tile in
    the same K order; the skips are provably inert)."""
    s, k = fsigma.shape
    ka, n = adj.shape
    assert ka == k and dist.shape == (s, n) and sigma.shape == (s, n), \
        (fsigma.shape, adj.shape, dist.shape, sigma.shape)
    common.check_push_tiles(s, n, bs, bn, bk, k=k)
    gi, gj, gk = s // bs, n // bn, k // bk

    f_occ = common.block_any(fsigma > 0, gi, bs, gk, bk)
    o_occ = common.block_any(dist < 0, gi, bs, gj, bn)
    step_arr = jnp.asarray(step, jnp.int32).reshape(1)

    grid_spec = common.push_grid_spec(gi, gj, gk, bs=bs, bn=bn, bk=bk,
                                      num_scalar_prefetch=3,
                                      acc_dtype=jnp.float32, n_state=2)
    new, dist_out, sig_out = pl.pallas_call(
        _counting_sweep_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.int32),
                   jax.ShapeDtypeStruct((s, n), jnp.float32)],
        compiler_params=common.sweep_compiler_params(),
        interpret=interpret,
    )(f_occ.astype(jnp.int32), o_occ.astype(jnp.int32), step_arr,
      fsigma, adj, dist, sigma)
    return new, dist_out, sig_out


# --------------------------------------------------------------------------
# fused multi-sweep persistent kernel (counting): the (dist, sigma) pair
# stays resident across sweeps — same skeleton, two state arrays
# --------------------------------------------------------------------------

def _fused_counting_kernel(meta_ref,                       # scalar prefetch
                           f_ref, a_ref, dist_ref, sig_ref,  # VMEM in
                           new_ref, dist_out_ref, sig_out_ref,  # VMEM out
                           prod_ref, stop_ref,             # SMEM out (gi,)
                           *, max_sweeps: int):
    step0 = meta_ref[0]
    n_run = meta_ref[1]
    a = a_ref[...].astype(jnp.float32)   # (n, n), resident throughout
    d0 = dist_ref[...]                   # (bs, n) int32
    sg0 = sig_ref[...]                   # (bs, n) f32

    def sweep(t, carry):
        done, prod, f8, d, sg, new8 = carry
        live = (done == 0) & (t < n_run)
        fs = jnp.where(f8 != 0, sg, 0.0)
        cand = jnp.dot(fs, a, precision=EXACT,
                       preferred_element_type=jnp.float32)
        new = (cand > 0) & (d < 0)
        any_new = jnp.any(new)
        upd = new & live
        d = jnp.where(upd, step0 + 1 + t, d)
        sg = jnp.where(upd, cand, sg)
        new8 = jnp.where(live, new.astype(jnp.int8), new8)
        f8 = jnp.where(live, new.astype(jnp.int8), f8)
        prod = prod + (live & any_new).astype(jnp.int32)
        done = done | (live & ~any_new).astype(jnp.int32)
        return done, prod, f8, d, sg, new8

    done, prod, _, d, sg, new8 = jax.lax.fori_loop(
        0, max_sweeps, sweep,
        (jnp.int32(0), jnp.int32(0), f_ref[...], d0, sg0,
         jnp.zeros(d0.shape, jnp.int8)))
    new_ref[...] = new8
    dist_out_ref[...] = d
    sig_out_ref[...] = sg
    i = pl.program_id(0)
    prod_ref[i] = prod
    stop_ref[i] = done


@functools.partial(jax.jit,
                   static_argnames=("bs", "max_sweeps", "interpret"))
def fused_counting_multisweep(frontier: jax.Array, adj: jax.Array,
                              state, step: jax.Array, n_run: jax.Array, *,
                              bs: int = 128, max_sweeps: int = 1,
                              interpret: bool = False):
    """Run up to ``n_run`` counting sweeps in one invocation — the
    counting instantiation of the fused multi-sweep skeleton (see the
    boolean ``fused_boolean_multisweep`` for the accounting contract).
    frontier (S, n) int8, adj (n, n) int8 resident, ``state`` the
    (dist int32, sigma f32) pair.  Path counts are integer-valued f32 —
    exact below 2^24 — so the single whole-row MXU matmul per sweep is
    bit-identical to the per-sweep kernel's K-tiled accumulation.
    Returns (new int8, (dist, sigma), prod int32, stopped bool)."""
    dist, sigma = state
    s, n = frontier.shape
    assert adj.shape == (n, n) and dist.shape == (s, n) \
        and sigma.shape == (s, n), (frontier.shape, adj.shape, dist.shape)
    assert s % bs == 0 and n % 128 == 0, (s, n, bs)
    gi = s // bs
    meta = jnp.stack([jnp.asarray(step, jnp.int32),
                      jnp.asarray(n_run, jnp.int32)])

    grid_spec = common.fused_grid_spec(gi, bs=bs, n=n, f_block=(bs, n),
                                       op_block=(n, n), n_state=2)
    new, dist_out, sig_out, prod, stop = pl.pallas_call(
        functools.partial(_fused_counting_kernel, max_sweeps=max_sweeps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.int8),
                   jax.ShapeDtypeStruct((s, n), jnp.int32),
                   jax.ShapeDtypeStruct((s, n), jnp.float32),
                   jax.ShapeDtypeStruct((gi,), jnp.int32),
                   jax.ShapeDtypeStruct((gi,), jnp.int32)],
        compiler_params=common.fused_compiler_params(),
        interpret=interpret,
    )(meta, frontier, adj, dist, sigma)
    return new, (dist_out, sig_out), jnp.max(prod), jnp.min(stop) > 0
