"""Public SSSP/APSP drivers — the paper's user-facing API.

``sssp(graph, source, method="auto")`` picks the execution path:

  * ``auto``  — THE direction-optimizing engine dispatcher
                (core/engine.py): sources tile into batches and every
                sweep runs in the cheapest form (push / pull / sparse)
                chosen by the engine cost model.  There is no separate
                density heuristic here — auto *is* the engine, so the
                public API can never drift from the dispatcher.
  * ``sovm``  — pin the edge-parallel sparse sweep (paper Alg. 2),
                single-source state, in-loop parent tracking.
  * ``bovm``  — pin the dense boolean matmul sweeps (paper Alg. 1 /
                MXU path).

Every result carries a shortest-path-tree ``parent`` array (any
in-neighbor at dist-1; max node id as the deterministic tie-break)
usable with :func:`repro.core.sovm.reconstruct_path`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import CSRGraph
from .bovm import bovm_msbfs
from .engine import EngineConfig, PreparedGraph, apsp_engine_blocks, \
    prepare_graph
from .sovm import sovm_msbfs, sovm_sssp
from .sweep import derive_parents


class SsspResult(NamedTuple):
    dist: jax.Array          # (n,) or (S, n) int32; -1 unreachable
    eccentricity: jax.Array  # sweeps executed that discovered something
    edges_touched: jax.Array
    # (n,) or (S, n) int32; -1 at sources/unreached.  None when the caller
    # opted out (parents=False — bulk distance consumers skip the
    # O(S · m_pad) derive_parents post-pass)
    parent: Optional[jax.Array]


def _auto_config(n_sources: int) -> EngineConfig:
    b = min(128, max(8, ((n_sources + 7) // 8) * 8))
    return EngineConfig(source_batch=b)


def _engine_sssp(g: Union[CSRGraph, PreparedGraph], sources: np.ndarray,
                 config: Optional[EngineConfig],
                 parents: bool) -> SsspResult:
    """Run sources through the engine dispatcher, attach parents."""
    pg = g if isinstance(g, PreparedGraph) else prepare_graph(g)
    config = config or _auto_config(len(sources))
    rows, ecc, touched = [], jnp.int32(0), jnp.float32(0.0)
    for _, dist, st in apsp_engine_blocks(pg, sources, config=config):
        rows.append(dist)
        ecc = jnp.maximum(ecc, st.sweeps)
        touched = touched + st.edges_touched
    dist = jnp.concatenate(rows, axis=0)
    return SsspResult(dist, ecc, touched,
                      derive_parents(pg.graph, dist) if parents else None)


def sssp(g: Union[CSRGraph, PreparedGraph], source: int, *,
         method: str = "auto", parents: bool = True,
         config: Optional[EngineConfig] = None) -> SsspResult:
    if method == "auto":
        r = _engine_sssp(g, np.asarray([source], np.int32), config, parents)
        return SsspResult(r.dist[0], r.eccentricity, r.edges_touched,
                          r.parent[0] if parents else None)
    graph = g.graph if isinstance(g, PreparedGraph) else g
    if method == "bovm":
        st = bovm_msbfs(graph.to_dense(), jnp.asarray([source], jnp.int32))
        return SsspResult(st.dist[0], st.step - 1, st.edges_touched,
                          derive_parents(graph, st.dist)[0] if parents
                          else None)
    assert method == "sovm", method
    st = sovm_sssp(graph, source,    # parent tracked in-loop (free)
                   rows=g.rows if isinstance(g, PreparedGraph) else None)
    return SsspResult(st.dist, st.sweeps, st.edges_touched, st.parent)


def multi_source(g: Union[CSRGraph, PreparedGraph],
                 sources: Sequence[int] | jax.Array, *,
                 method: str = "auto", parents: bool = True,
                 config: Optional[EngineConfig] = None) -> SsspResult:
    srcs = np.asarray(sources, np.int32)
    if method == "auto":
        return _engine_sssp(g, srcs, config, parents)
    graph = g.graph if isinstance(g, PreparedGraph) else g
    if method == "bovm":
        st = bovm_msbfs(graph.to_dense(), jnp.asarray(srcs))
        return SsspResult(st.dist, st.step - 1, st.edges_touched,
                          derive_parents(graph, st.dist) if parents
                          else None)
    assert method == "sovm", method
    st = sovm_msbfs(graph, jnp.asarray(srcs),   # parent tracked in-loop
                    rows=g.rows if isinstance(g, PreparedGraph) else None)
    return SsspResult(st.dist, jnp.max(st.sweeps),
                      jnp.sum(st.edges_touched), st.parent)


def apsp(g: Union[CSRGraph, PreparedGraph], *, block: int = 128,
         method: str = "auto"):
    """All-pairs via blocked multi-source sweeps.  Yields (sources, dist)
    blocks to avoid materializing the full (n, n) matrix for large n.

    method='auto' prepares the graph once so engine operands are shared
    across every block."""
    if method == "auto" and not isinstance(g, PreparedGraph):
        g = prepare_graph(g)
    n = (g.graph if isinstance(g, PreparedGraph) else g).n_nodes
    for lo in range(0, n, block):
        srcs = jnp.arange(lo, min(lo + block, n), dtype=jnp.int32)
        yield srcs, multi_source(g, srcs, method=method, parents=False).dist


def apsp_dense(g: Union[CSRGraph, PreparedGraph], *, block: int = 128,
               method: str = "auto"):
    """Materialized APSP (small graphs / tests)."""
    rows = [np.asarray(d) for _, d in apsp(g, block=block, method=method)]
    return np.concatenate(rows, axis=0)
