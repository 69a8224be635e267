"""Frontier representations for DAWN.

The paper stores frontiers as byte booleans (GPU memory is byte-addressable,
§3.4).  On TPU we keep two forms:

  * unpacked int8/bool  — feeds the MXU matmul path (BOVM) and segment ops;
  * bit-packed uint32   — 32 nodes/word, used for cross-device collectives
    and for the memory-model benchmark (beyond-paper optimization: 8–32×
    collective-byte reduction, DESIGN.md §9.3).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WORD = 32
UNREACHED = jnp.int32(-1)


def packed_width(n: int) -> int:
    return (n + WORD - 1) // WORD


@jax.jit
def pack_bits(x: jax.Array) -> jax.Array:
    """(..., n) bool/int -> (..., ceil(n/32)) uint32 (little-endian bits).
    Jitted so the widen-shift-sum fuses into one pass: called eagerly on
    an (n_pad, n_pad) adjacency it would otherwise materialize two
    uint32 copies of it."""
    n = x.shape[-1]
    w = packed_width(n)
    pad = w * WORD - n
    xb = x.astype(jnp.uint32)
    if pad:
        xb = jnp.concatenate(
            [xb, jnp.zeros(x.shape[:-1] + (pad,), jnp.uint32)], axis=-1)
    xb = xb.reshape(x.shape[:-1] + (w, WORD))
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    return jnp.sum(xb << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(p: jax.Array, n: int) -> jax.Array:
    """(..., w) uint32 -> (..., n) bool."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (p[..., :, None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(p.shape[:-1] + (p.shape[-1] * WORD,))
    return flat[..., :n].astype(jnp.bool_)


def popcount(p: jax.Array) -> jax.Array:
    """Number of set bits per packed row (frontier occupancy)."""
    x = p
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return jnp.sum((x * jnp.uint32(0x01010101)) >> 24, axis=-1,
                   dtype=jnp.int32)


def one_hot_frontier(sources: jax.Array, n: int,
                     dtype=jnp.bool_) -> jax.Array:
    """(S,) int source ids -> (S, n) boolean frontier matrix."""
    return (jnp.arange(n)[None, :] == sources[:, None]).astype(dtype)
