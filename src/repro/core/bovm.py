"""BOVM — Boolean Vector/Matrix Operation (paper Alg. 1), TPU form.

The paper walks CSC columns with a per-element early exit.  The TPU-native
equivalent is a {0,1}-valued matmul: a sweep computes

    counts = F @ A        (S sources batched; MXU-friendly)
    hits   = counts > 0
    new    = hits & ~visited          # Theorem 3.2 skip
    dist   = where(new, step, dist)   # first hit IS the shortest path

and the per-element early exit becomes tile-level skipping inside the
Pallas kernel (kernels/bovm).  Values are exact: counts ≤ n < 2^24 so f32
accumulation is lossless; int8 inputs with int32 accumulation are also
supported.

This module is a thin boolean-semiring instantiation of the shared sweep
layer: ``bovm_msbfs`` pins the dense PUSH form of
:func:`repro.core.sweep.boolean_forms` into :func:`repro.core.sweep.sweep_loop`
(Fact-1 convergence, Eq. 5 work counter and all).  The batched,
direction-optimizing production path is core/engine.py.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import sweep as S
from .frontier import UNREACHED, one_hot_frontier


class DawnState(NamedTuple):
    frontier: jax.Array   # (S, n) int8 — discovered in the previous sweep
    dist: jax.Array       # (S, n) int32, UNREACHED = -1
    step: jax.Array       # scalar int32, current path length
    done: jax.Array       # scalar bool — Fact 1 fired
    edges_touched: jax.Array  # scalar float — work counter (Eq. 5)


def bovm_sweep(adj: jax.Array, frontier: jax.Array, visited: jax.Array,
               *, accum_dtype=jnp.float32,
               matmul_fn=None) -> jax.Array:
    """One boolean sweep: new = (frontier @ adj > 0) & ~visited.

    adj      : (n, n) int8/bool dense adjacency (row = src, col = dst)
    frontier : (S, n) bool
    visited  : (S, n) bool
    matmul_fn: optional kernel override (e.g. the Pallas tile-skip kernel),
               signature (F_int, A_int) -> counts.
    """
    if matmul_fn is None:
        f = frontier.astype(accum_dtype)
        a = adj.astype(accum_dtype)
        counts = jax.lax.dot_general(
            f, a, (((1,), (0,)), ((), ())),
            preferred_element_type=accum_dtype)
    else:
        counts = matmul_fn(frontier, adj)
    hits = counts > 0
    return hits & ~visited


@partial(jax.jit, static_argnames=("max_steps", "accum_dtype"))
def bovm_msbfs(adj: jax.Array, sources: jax.Array, *,
               max_steps: Optional[int] = None,
               accum_dtype=jnp.float32) -> DawnState:
    """Multi-source DAWN over a dense adjacency.

    adj     : (n, n) int8 dense adjacency
    sources : (S,) int32
    returns : DawnState with dist (S, n); dist[s, sources[s]] = 0.
    """
    n = adj.shape[0]
    s = sources.shape[0]
    max_steps = n if max_steps is None else max_steps

    f0 = one_hot_frontier(sources, n, dtype=jnp.int8)
    dist0 = jnp.where(f0 != 0, 0, jnp.full((s, n), UNREACHED))
    deg = jnp.sum(adj.astype(jnp.float32), axis=1)  # out-degrees

    # dense boolean PUSH only: the pull/sparse slots get dummies that the
    # pinned forced_dir never traces
    push, _, _ = S.boolean_forms(
        adj, jnp.zeros((1, 1), jnp.uint32), None, n_pad=n, s=s,
        use_kernel=False, accum_dtype=accum_dtype)

    st = S.sweep_loop((push,), S.make_state(f0, dist0, n_forms=1),
                      max_steps=max_steps, deg=deg)
    return DawnState(frontier=st.frontier, dist=st.dist, step=st.step,
                     done=st.done, edges_touched=st.edges_touched)


def bovm_sssp(adj: jax.Array, source, **kw) -> DawnState:
    """Single-source convenience wrapper (S = 1)."""
    src = jnp.atleast_1d(jnp.asarray(source, jnp.int32))
    st = bovm_msbfs(adj, src, **kw)
    return DawnState(frontier=st.frontier[0], dist=st.dist[0],
                     step=st.step, done=st.done,
                     edges_touched=st.edges_touched)
