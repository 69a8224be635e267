"""Graph500 Kronecker graphs made on the device, and their CSR assembly.

The generator follows the Graph500 specification's reference code
(``kronecker_generator.m``): every edge picks one quadrant per level of
``scale`` with probabilities A, B, C and D = 1 - A - B - C, then the
vertex labels are relabelled by a random permutation.  The spec also
shuffles the edge list; the CSR below sorts its lanes, so a shuffle could
change no array of it and is left out.

``assemble_csr`` turns the edge list into the ``CSRGraph`` fields that
``CSRGraph.from_edges`` would build from it (undirected: both directions;
self-loops and duplicates removed; lanes sorted by (src, dst); sentinel
``n`` in the padded lanes), with two device sorts and no host loop.  The
lane count ``m_pad`` comes from the configuration, not from the data, so
every seed compiles to the same shapes and a warm compile cache serves
every run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number, wider than 32 bits
    included: the low 32 bits make the key, the high bits are folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def quadrant_bits(key, m: int, *, scale: int, a: float, b: float,
                  c: float):
    """(i, j) int32 endpoints of ``m`` edges before relabelling: at each
    level the edge falls in quadrant A, B, C or D of the adjacency."""
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab

    def level(ib, ij):
        i, j = ij
        k_i, k_j = jax.random.split(jax.random.fold_in(key, ib))
        i_bit = jax.random.uniform(k_i, (m,)) > ab
        j_bit = jax.random.uniform(k_j, (m,)) > jnp.where(i_bit, c_norm,
                                                          a_norm)
        return (i | (i_bit.astype(jnp.int32) << ib),
                j | (j_bit.astype(jnp.int32) << ib))

    zero = jnp.zeros(m, jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


def relabelling(key, n: int) -> jax.Array:
    """The random permutation of vertex labels."""
    return jax.random.permutation(jax.random.fold_in(key, 1),
                                  n).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "a",
                                             "b", "c"))
def kronecker_edges(key, *, scale: int, edge_factor: int, a: float,
                    b: float, c: float):
    """(src, dst) int32 edge list of a Graph500 Kronecker graph with
    ``edge_factor << scale`` edges, relabelled by a random permutation."""
    i, j = quadrant_bits(jax.random.fold_in(key, 0), edge_factor << scale,
                         scale=scale, a=a, b=b, c=c)
    perm = relabelling(key, 1 << scale)
    return perm[i], perm[j]


@functools.partial(jax.jit, static_argnames=("n",))
def _sorted_lanes(src, dst, *, n: int):
    src, dst = jnp.concatenate([src, dst]), jnp.concatenate([dst, src])
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    dup = jnp.concatenate([jnp.zeros(1, bool),
                           (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])])
    drop = dup | (src == dst)
    # dropped lanes become the sentinel (n, n) and sort to the end
    src, dst = jax.lax.sort((jnp.where(drop, n, src),
                             jnp.where(drop, n, dst)), num_keys=2)
    indptr = jnp.searchsorted(src, jnp.arange(n + 1, dtype=jnp.int32),
                              side="left").astype(jnp.int32)
    return src, dst, indptr, jnp.sum(~drop, dtype=jnp.int32)


def assemble_csr(src, dst, n: int, m_pad: int) -> CSRGraph:
    """The ``CSRGraph`` that ``CSRGraph.from_edges`` builds from this
    edge list, symmetrized, with ``pad_to=m_pad``.  Raises when the graph
    has more lanes than ``m_pad``."""
    src_s, dst_s, indptr, m = _sorted_lanes(
        jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32), n=n)
    m = int(m)
    if m > m_pad or m_pad > src_s.shape[0]:
        raise ValueError(f"graph has {m} lanes; the configuration's "
                         f"m_pad={m_pad} must lie in [{m}, "
                         f"{src_s.shape[0]}]")
    src_s, dst_s = src_s[:m_pad], dst_s[:m_pad]
    # a symmetric edge set's CSC equals its CSR, array for array
    return CSRGraph(indptr=indptr, indices=dst_s, src=src_s, dst=dst_s,
                    indptr_t=indptr, indices_t=dst_s, n_nodes=n,
                    n_edges=m, m_pad=m_pad)


def graph500(seed: int, *, scale: int, edge_factor: int, a: float, b: float,
             c: float, m_pad: int) -> CSRGraph:
    """The undirected Graph500 Kronecker graph of ``seed`` as a
    ``CSRGraph`` with ``m_pad`` lanes, built on the default device."""
    src, dst = kronecker_edges(jax.random.fold_in(seed_key(seed), 0),
                               scale=scale, edge_factor=edge_factor, a=a,
                               b=b, c=c)
    return assemble_csr(src, dst, 1 << scale, m_pad)


def build(seed: int, params: dict) -> CSRGraph:
    """The configuration's graph: ``params`` holds ``scale``,
    ``edge_factor``, ``a``, ``b``, ``c`` and ``m_pad``."""
    return graph500(seed, **{k: params[k] for k in (
        "scale", "edge_factor", "a", "b", "c", "m_pad")})
