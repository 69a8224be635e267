"""SOVM — Sparse Optimized boolean Vector-Matrix operation (paper Alg. 2).

The paper merges CSR rows of the frontier nodes (Eq. 9: the sweep result is
the union of the frontier rows), skipping targets already in the result
vector.  The TPU-native fixed-shape equivalent is edge-parallel masked
propagation with scatter-max:

    active[e] = frontier[src[e]]                       # gather
    hits      = scatter_or(active -> dst)              # Eq. 9 union
    new       = hits & (dist == UNREACHED)             # Thm 3.2 skip
    dist      = where(new, step, dist)

Padded edges carry src = dst = n (sentinel): ``frontier[n]`` is pinned
False and ``dist[n]`` is pinned 0 (visited), so padding is inert without
masks.

This module is the boolean-semiring SPARSE instantiation of the shared
sweep layer (core/sweep.py): ``sovm_sssp`` pins the sparse form — on
the graph's destination rows (``sweep.dst_rows``: the lanes above grouped
by ``dst``, one scatter update per row of lanes), with in-loop parent
tracking — into the one ``sweep_loop`` driver.  Work
accounting: the true SOVM work per sweep is sum(out_degree[frontier])
(Eq. 10 → total = E_wcc(i)); the driver tracks it exactly in
``edges_touched`` so the complexity claims are empirically checkable even
though the fixed-shape scatter touches all m lanes.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..graph.csr import CSRGraph
from . import sweep as S
from .frontier import UNREACHED


class SovmState(NamedTuple):
    frontier: jax.Array        # (n,) int8
    dist: jax.Array            # (n,) int32
    parent: jax.Array          # (n,) int32 — path reconstruction
    step: jax.Array
    done: jax.Array
    edges_touched: jax.Array   # float32 scalar — Eq. 10 counter
    sweeps: jax.Array          # int32 — equals ε(i) at exit


def sovm_sweep(g: CSRGraph, frontier: jax.Array, dist: jax.Array):
    """One frontier expansion. Returns (new_frontier, parent_candidates)."""
    n = g.n_nodes
    active = frontier[g.src] != 0                             # (m_pad,)
    hits = jnp.zeros(n + 1, jnp.bool_).at[g.dst].max(active)  # scatter-OR
    new = hits & (dist == UNREACHED)
    # parent: any active in-neighbor (max src id wins — deterministic)
    pcand = jnp.full(n + 1, -1, jnp.int32).at[g.dst].max(
        jnp.where(active, g.src, -1))
    return new, pcand


def sovm_sssp(g: CSRGraph, source, *, rows=None,
              max_steps: Optional[int] = None) -> SovmState:
    """DAWN-SOVM single-source shortest paths.  O(E_wcc(i)) useful work.

    ``rows`` is ``g``'s destination-row layout (:func:`sweep.dst_rows`,
    cached as ``PreparedGraph.rows``); pass it when searching one graph
    more than once, else each call builds it."""
    return _sovm_sssp(g, _rows_of(g, rows), source, max_steps=max_steps)


def sovm_msbfs(g: CSRGraph, sources: jax.Array, *, rows=None,
               max_steps: Optional[int] = None) -> SovmState:
    """Multi-source SOVM via vmap over sources (S small) — the sparse-graph
    analogue of bovm_msbfs.  For large S on dense graphs prefer the BOVM
    matmul path.  ``rows`` as for :func:`sovm_sssp`."""
    return _sovm_msbfs(g, _rows_of(g, rows), jnp.asarray(sources, jnp.int32),
                       max_steps=max_steps)


def _rows_of(g: CSRGraph, rows):
    return S.dst_rows(g.indptr_t, g.indices_t, n_real=g.n_nodes) \
        if rows is None else rows


@partial(jax.jit, static_argnames=("max_steps",))
def _sovm_sssp(g: CSRGraph, rows, source, *,
               max_steps: Optional[int] = None) -> SovmState:
    n = g.n_nodes
    max_steps = n if max_steps is None else max_steps
    src = jnp.asarray(source, jnp.int32)

    frontier0 = jnp.zeros(n + 1, jnp.int8).at[src].set(1)
    dist0 = jnp.full(n + 1, UNREACHED).at[src].set(0).at[n].set(0)
    parent0 = jnp.full(n + 1, -1, jnp.int32)
    deg = jnp.concatenate([g.out_degrees().astype(jnp.float32),
                           jnp.zeros(1, jnp.float32)])

    _, _, sparse = S.boolean_forms(
        jnp.zeros((1, 1), jnp.int8), jnp.zeros((1, 1), jnp.uint32), rows,
        n_pad=n + 1, s=1, track_parent=True)

    st = S.sweep_loop((sparse,), S.make_state(frontier0, dist0, parent0,
                                              n_forms=1),
                      max_steps=max_steps, deg=deg, forced_dir=0)
    # drop sentinel row
    return SovmState(st.frontier[:n], st.dist[:n], st.parent[:n],
                     st.step, st.done, st.edges_touched, st.sweeps)


@partial(jax.jit, static_argnames=("max_steps",))
def _sovm_msbfs(g: CSRGraph, rows, sources: jax.Array, *,
                max_steps: Optional[int] = None) -> SovmState:
    return jax.vmap(lambda s: _sovm_sssp(g, rows, s,
                                         max_steps=max_steps))(sources)


def reconstruct_path(parent, source: int, target: int, max_len: int):
    """Host-side path reconstruction from the parent array."""
    import numpy as np
    parent = np.asarray(parent)
    path = [target]
    cur = target
    for _ in range(max_len):
        if cur == source:
            break
        cur = int(parent[cur])
        if cur < 0:
            return None
        path.append(cur)
    return path[::-1] if path[-1] is not None and path[0] == source else path[::-1]
