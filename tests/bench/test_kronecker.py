"""The benchmark's device-side Graph500 generator and CSR assembly."""
import jax
import numpy as np
import pytest

from bench.generators import kronecker as K
from repro.graph.csr import CSRGraph, symmetrize

ABC = dict(a=0.57, b=0.19, c=0.19)
FIELDS = ("indptr", "indices", "src", "dst", "indptr_t", "indices_t")


def _edges(seed, scale, edge_factor=16):
    return K.kronecker_edges(K.seed_key(seed), scale=scale,
                             edge_factor=edge_factor, **ABC)


@pytest.mark.parametrize("scale,seed", [(4, 0), (6, 1), (8, 2),
                                        (9, 2**33 + 7)])
def test_assembly_equals_from_edges(scale, seed):
    src, dst = _edges(seed, scale)
    n = 1 << scale
    want = CSRGraph.from_edges(*symmetrize(np.asarray(src), np.asarray(dst)),
                               n)
    got = K.assemble_csr(src, dst, n, want.m_pad)
    assert (got.n_nodes, got.n_edges, got.m_pad) == \
        (want.n_nodes, want.n_edges, want.m_pad)
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)), f)


def test_assembly_pads_to_the_configured_lanes():
    src, dst = _edges(3, 7)
    n = 1 << 7
    tight = K.assemble_csr(src, dst, n, 128 * 20)
    want = CSRGraph.from_edges(*symmetrize(np.asarray(src), np.asarray(dst)),
                               n, pad_to=128 * 20)
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(tight, f)),
                                      np.asarray(getattr(want, f)), f)
    with pytest.raises(ValueError, match="m_pad"):
        K.assemble_csr(src, dst, n, 128)


def test_relabelling_is_a_permutation_drawn_from_the_seed():
    n = 1 << 10
    perms = [np.asarray(K.relabelling(K.seed_key(s), n)) for s in (5, 5, 6)]
    for p in perms:
        np.testing.assert_array_equal(np.sort(p), np.arange(n))
    np.testing.assert_array_equal(perms[0], perms[1])
    assert (perms[0] != perms[2]).mean() > 0.9


def test_edges_are_the_quadrant_bits_relabelled():
    key, scale = K.seed_key(11), 8
    src, dst = _edges(11, scale)
    i, j = K.quadrant_bits(jax.random.fold_in(key, 0), 16 << scale,
                           scale=scale, **ABC)
    perm = np.asarray(K.relabelling(key, 1 << scale))
    np.testing.assert_array_equal(np.asarray(src), perm[np.asarray(i)])
    np.testing.assert_array_equal(np.asarray(dst), perm[np.asarray(j)])


def test_quadrant_probabilities():
    # one level: the edge lands in A, B, C, D with the spec's shares
    i, j = K.quadrant_bits(K.seed_key(3), 200_000, scale=1, **ABC)
    q = np.bincount(2 * np.asarray(i) + np.asarray(j), minlength=4) / 200_000
    np.testing.assert_allclose(q, [0.57, 0.19, 0.19, 0.05], atol=0.005)


def test_hubs_move_with_the_seed():
    # the unrelabelled generator puts its hub at vertex 0
    hubs = set()
    for seed in range(4):
        src, _ = _edges(seed, 10)
        hubs.add(int(np.argmax(np.bincount(np.asarray(src),
                                           minlength=1 << 10))))
    assert len(hubs) > 1


def test_large_seeds_give_distinct_graphs():
    a, _ = _edges(2**31 + 1, 6)
    b, _ = _edges(2**31 + 1 + 2**32, 6)
    c, _ = _edges(2**31 + 1, 6)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        K.seed_key(-1)
