"""Pallas kernel validation (interpret=True): the semiring kernel registry,
shape/dtype sweeps + full BFS drivers vs the pure-jnp oracles for the
boolean kernels, and the tropical min-plus kernels vs their oracles, the
dense reference forms, and scipy Dijkstra.

This module runs without hypothesis (only the property-based test is
guarded) so CI can execute it as its own fast kernel-layer job step.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # seeded variants below always run regardless
    HAVE_HYPOTHESIS = False

from repro.graph import generators as gen
from repro.core import WeightedConfig, pack_bits, weighted_apsp
from oracles import bfs_dists, dijkstra_dists
from repro.kernels import common, registry
from repro.kernels.bovm import (fused_sweep, packed_pull_sweep,
                                packed_push_sweep, fused_boolean_multisweep,
                                sweep_ref, packed_pull_ref, packed_push_ref,
                                msbfs_kernel, msbfs_packed,
                                pack_adjacency_pull)
from repro.kernels.tropical import (fused_minplus_sweep,
                                    fused_minplus_multisweep,
                                    sparse_relax_sweep,
                                    minplus_sweep_ref, sparse_relax_ref)
from repro.kernels.counting import (fused_counting_sweep,
                                    fused_counting_multisweep,
                                    counting_sweep_ref)


def _random_state(rng, s, n, density=0.05, visited=0.2):
    f = (rng.random((s, n)) < density).astype(np.int8)
    dist = np.where(rng.random((s, n)) < visited, 1, -1).astype(np.int32)
    return jnp.asarray(f), jnp.asarray(dist)


# --------------------------------------------------------------------------
# the registry: one substrate, N semirings
# --------------------------------------------------------------------------

def test_registry_has_every_semiring():
    assert registry.available() == ("boolean", "counting", "tropical")
    assert registry.has("boolean") and registry.has("tropical")
    assert registry.has("counting")
    assert set(registry.get("boolean").forms) == {"push", "push_f32",
                                                  "pull"}
    assert set(registry.get("tropical").forms) == {"dense", "sparse"}
    assert set(registry.get("counting").forms) == {"push"}


def test_registry_has_fused_multisweep_capability():
    """Every semiring ships the fused multi-sweep persistent form under
    the same key its per-sweep kernel uses — the capability
    sweep.resolve_fused_steps consults."""
    assert set(registry.get("boolean").fused_forms) == {"push"}
    assert set(registry.get("tropical").fused_forms) == {"dense"}
    assert set(registry.get("counting").fused_forms) == {"push"}
    assert registry.get("boolean").fused_forms["push"] \
        is fused_boolean_multisweep
    assert registry.get("tropical").fused_forms["dense"] \
        is fused_minplus_multisweep
    assert registry.get("counting").fused_forms["push"] \
        is fused_counting_multisweep


def test_registry_accepts_semiring_objects():
    from repro.core import BOOLEAN, COUNTING, TROPICAL
    # the boolean kernel push is the bit-packed word sweep (no f32 GEMM);
    # the old MXU GEMM survives under the explicit "push_f32" key
    assert registry.get(BOOLEAN).forms["push"] is packed_push_sweep
    assert registry.get(BOOLEAN).forms["push_f32"] is fused_sweep
    assert registry.get(TROPICAL).forms["dense"] is fused_minplus_sweep
    assert registry.get(COUNTING).forms["push"] is fused_counting_sweep
    with pytest.raises(KeyError, match="min_label"):
        registry.get("min_label")    # no kernels for label propagation


def test_vmem_budgets_under_per_core_limit():
    """Every registered kernel's default tiles sit well under ~16 MB."""
    assert registry.get("boolean").vmem_bytes(form="push") \
        < common.VMEM_BUDGET_BYTES // 4
    assert registry.get("boolean").vmem_bytes(form="pull") \
        < common.VMEM_BUDGET_BYTES // 4
    assert registry.get("tropical").vmem_bytes(form="dense") \
        < common.VMEM_BUDGET_BYTES // 4
    assert registry.get("tropical").vmem_bytes(form="sparse", s=128,
                                               n_pad=2048) \
        < common.VMEM_BUDGET_BYTES // 4
    assert registry.get("counting").vmem_bytes(form="push") \
        < common.VMEM_BUDGET_BYTES // 4
    assert registry.get("boolean").vmem_bytes(form="push_f32") \
        < common.VMEM_BUDGET_BYTES // 4


def test_fused_vmem_scales_with_whole_operand():
    """The fused forms hold the WHOLE operand resident: their cost is a
    function of n, grows quadratically, and the default paddings still
    fit the 16 MB budget — exactly what resolve_fused_steps gates on."""
    for semi, mult in (("boolean", 1 / 8), ("tropical", 4),
                       ("counting", 1)):
        ks = registry.get(semi)
        small = ks.vmem_bytes(form="fused", bs=128, n=1152)
        big = ks.vmem_bytes(form="fused", bs=128, n=4 * 1152)
        assert small < common.VMEM_BUDGET_BYTES, (semi, small)
        # superlinear in n: the resident whole-operand term scales n^2
        # (the per-row state term alone would only scale linearly, x4)
        assert big > small * 4, (semi, small, big)
        assert small > 1152 * 1152 * mult, (semi, small)
    # the gate actually trips for an operand that cannot fit
    import repro.core.sweep as S
    assert S.resolve_fused_steps("tropical", "dense", fused_steps=-1,
                                 max_steps=64, use_kernel=True,
                                 n_pad=8192, bs=128) is None
    assert S.resolve_fused_steps("tropical", "dense", fused_steps=-1,
                                 max_steps=64, use_kernel=True,
                                 n_pad=1152, bs=128) == 64
    assert S.resolve_fused_steps("tropical", "dense", fused_steps=4,
                                 max_steps=64, use_kernel=True,
                                 n_pad=1152, bs=128) == 4
    # reference path and unregistered semirings never fuse
    assert S.resolve_fused_steps("tropical", "dense", fused_steps=-1,
                                 max_steps=64, use_kernel=False,
                                 n_pad=1152, bs=128) is None
    assert S.resolve_fused_steps("min_label", "push", fused_steps=-1,
                                 max_steps=64, use_kernel=True,
                                 n_pad=1152, bs=128) is None


# --------------------------------------------------------------------------
# boolean semiring kernels (paper Algs. 1/2)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("s,n,bs,bn,bk", [
    (64, 256, 64, 128, 128),
    (128, 512, 128, 128, 256),
    (8, 128, 8, 128, 128),
    (256, 384, 64, 128, 128),
])
def test_fused_sweep_shapes(s, n, bs, bn, bk):
    rng = np.random.default_rng(s * n)
    g = gen.erdos_renyi(n, 4.0, seed=n, directed=False)
    adj = jnp.asarray(np.asarray(g.to_dense_padded(n)), jnp.int8)
    f, dist = _random_state(rng, s, n)
    new_k, dist_k = fused_sweep(f, adj, dist, 5, bs=bs, bn=bn, bk=bk,
                                interpret=True)
    new_r, dist_r = sweep_ref(f, adj, dist, 5)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


@pytest.mark.parametrize("s,n,bs,bn,wk", [
    (8, 256, 8, 128, 8),
    (16, 512, 8, 128, 16),
    (32, 128, 16, 128, 4),
])
def test_packed_pull_shapes(s, n, bs, bn, wk):
    rng = np.random.default_rng(s + n)
    g = gen.erdos_renyi(n, 5.0, seed=n + 1, directed=True)
    adj = jnp.asarray(np.asarray(g.to_dense_padded(n)), jnp.int8)
    ap = pack_adjacency_pull(adj)
    f, dist = _random_state(rng, s, n)
    fp = pack_bits(f > 0)
    new_k, dist_k = packed_pull_sweep(fp, ap, dist, 3, bs=bs, bn=bn, wk=wk,
                                      interpret=True)
    new_r, dist_r = packed_pull_ref(fp, ap, dist, 3)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


@pytest.mark.parametrize("s,n,bs,bn,wk", [
    (128, 256, 128, 128, 8),
    (64, 512, 64, 128, 16),
    (8, 128, 8, 128, 4),
    (256, 384, 128, 128, 4),     # ragged: n not a multiple of bn*2
])
def test_packed_push_shapes(s, n, bs, bn, wk):
    """The bit-packed push drives the same word-AND/OR math as pull: the
    packed frontier rows hit the packed in-neighbour words, so the shared
    packed_pull_ref is its oracle too."""
    rng = np.random.default_rng(3 * s + n)
    g = gen.erdos_renyi(n, 5.0, seed=n + 2, directed=True)
    adj = jnp.asarray(np.asarray(g.to_dense_padded(n)), jnp.int8)
    ap = pack_adjacency_pull(adj)
    f, dist = _random_state(rng, s, n)
    fp = pack_bits(f > 0)
    new_k, dist_k = packed_push_sweep(fp, ap, dist, 3, bs=bs, bn=bn, wk=wk,
                                      interpret=True)
    new_r, dist_r = packed_push_ref(fp, ap, dist, 3)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


def test_packed_push_matches_f32_push():
    """Packed word push == the f32 GEMM push it replaces, bit for bit."""
    rng = np.random.default_rng(11)
    n, s = 256, 64
    adj = jnp.asarray((rng.random((n, n)) < 0.03).astype(np.int8))
    f, dist = _random_state(rng, s, n)
    new_p, dist_p = packed_push_sweep(pack_bits(f > 0),
                                      pack_adjacency_pull(adj), dist, 5,
                                      bs=64, bn=128, wk=8, interpret=True)
    new_g, dist_g = fused_sweep(f, adj, dist, 5, bs=64, bn=128, bk=128,
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(new_p), np.asarray(new_g))
    np.testing.assert_array_equal(np.asarray(dist_p), np.asarray(dist_g))


def test_packed_push_tile_skip_preserves_semantics():
    """Adversarial occupancy: one frontier word block and one unreached
    block live — every skipped (i, j, k) tile must be provably inert."""
    n, s = 512, 128
    rng = np.random.default_rng(5)
    adj = jnp.asarray((rng.random((n, n)) < 0.02).astype(np.int8))
    f = np.zeros((s, n), np.int8)
    f[:, :32] = 1                       # frontier in the first word block
    dist = np.zeros((s, n), np.int32)   # almost everything settled…
    dist[:, 256:] = -1                  # …except the last j tiles
    fp = pack_bits(jnp.asarray(f) > 0)
    ap = pack_adjacency_pull(adj)
    new_k, dist_k = packed_push_sweep(fp, ap, jnp.asarray(dist), 4,
                                      bs=128, bn=128, wk=4, interpret=True)
    new_r, dist_r = packed_push_ref(fp, ap, jnp.asarray(dist), 4)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


# --------------------------------------------------------------------------
# fused multi-sweep persistent kernels (all semirings, one skeleton)
# --------------------------------------------------------------------------

def _per_sweep_boolean(f, ap, dist, step, n_run):
    """Oracle: n_run per-sweep packed pushes with the fused accounting
    contract (prod = productive sweeps, stopped = converged mid-block)."""
    prod, stopped = 0, False
    new = jnp.zeros_like(dist, dtype=jnp.int8)
    for t in range(n_run):
        if stopped:
            break
        new, dist = packed_push_ref(pack_bits(new != 0) if t else f,
                                    ap, dist, step + 1 + t)
        if bool(jnp.any(new != 0)):
            prod += 1
        else:
            stopped = True
    return new, dist, prod, stopped


@pytest.mark.parametrize("n_run", [1, 2, 3, 7])
def test_fused_boolean_multisweep_matches_per_sweep(n_run):
    rng = np.random.default_rng(n_run)
    n, s = 256, 128
    adj = jnp.asarray((rng.random((n, n)) < 0.02).astype(np.int8))
    ap = pack_adjacency_pull(adj)
    f = jnp.asarray((rng.random((s, n)) < 0.02).astype(np.int8))
    dist = jnp.where(f != 0, 3, -1).astype(jnp.int32)
    new_k, dist_k, prod_k, stop_k = fused_boolean_multisweep(
        f, ap, dist, 3, n_run, bs=128, max_sweeps=n_run, interpret=True)
    new_r, dist_r, prod_r, stop_r = _per_sweep_boolean(
        pack_bits(f != 0), ap, dist, 3, n_run)
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    assert int(prod_k) == prod_r and bool(stop_k) == stop_r


def test_fused_boolean_multisweep_converges_mid_block():
    """Fact 1 inside the block: a 3-hop path exhausts after 3 productive
    sweeps of an 8-sweep block — the kernel must report stopped with
    prod == 3 and leave dist at the fixpoint."""
    n, s = 128, 8
    src = np.array([0, 1, 2])
    dst = np.array([1, 2, 3])
    adj = np.zeros((n, n), np.int8)
    adj[src, dst] = 1
    ap = pack_adjacency_pull(jnp.asarray(adj))
    f = np.zeros((s, n), np.int8)
    f[:, 0] = 1
    dist = np.full((s, n), -1, np.int32)
    dist[:, 0] = 0
    new, dist_out, prod, stop = fused_boolean_multisweep(
        jnp.asarray(f), ap, jnp.asarray(dist), 0, 8, bs=8, max_sweeps=8,
        interpret=True)
    assert int(prod) == 3 and bool(stop)
    assert np.asarray(new).sum() == 0          # final frontier is empty
    expect = np.full(n, -1, np.int32)
    expect[:4] = [0, 1, 2, 3]
    np.testing.assert_array_equal(np.asarray(dist_out)[0], expect)


def test_fused_boolean_multisweep_not_converged_keeps_frontier():
    """A block that ends mid-BFS reports stopped=False, prod == n_run and
    a live packed frontier equal to the last sweep's discoveries."""
    n, s = 128, 8
    adj = np.zeros((n, n), np.int8)
    adj[np.arange(20), np.arange(1, 21)] = 1      # a 20-hop path
    ap = pack_adjacency_pull(jnp.asarray(adj))
    f = np.zeros((s, n), np.int8)
    f[:, 0] = 1
    dist = np.full((s, n), -1, np.int32)
    dist[:, 0] = 0
    new, dist_out, prod, stop = fused_boolean_multisweep(
        jnp.asarray(f), ap, jnp.asarray(dist), 0, 5, bs=8, max_sweeps=5,
        interpret=True)
    assert int(prod) == 5 and not bool(stop)
    assert np.asarray(new)[0, 5] == 1 and np.asarray(new)[0].sum() == 1
    assert np.asarray(dist_out)[0, 5] == 5


def test_fused_minplus_multisweep_matches_per_sweep():
    """Tropical fused block == iterated per-sweep min-plus reference."""
    rng = np.random.default_rng(17)
    n, s = 256, 64
    mask = rng.random((n, n)) < 0.03
    w = np.where(mask, rng.integers(1, 8, (n, n)).astype(np.float32),
                 np.inf)
    np.fill_diagonal(w, np.inf)
    dist = np.full((s, n), np.inf, np.float32)
    dist[np.arange(s), np.arange(s)] = 0.0
    f = (dist == 0).astype(np.int8)
    wj = jnp.asarray(w)
    d = jnp.asarray(dist)
    new_k, dist_k, prod_k, stop_k = fused_minplus_multisweep(
        jnp.asarray(f), wj, d, 0, 6, bs=64, max_sweeps=6, interpret=True)
    # reference: per-sweep dense min-plus with the same convergence rule
    fr, dr, prod_r, stop_r = jnp.asarray(f), d, 0, False
    for _ in range(6):
        if stop_r:
            break
        fd = jnp.where(fr != 0, dr, jnp.inf)
        nd = minplus_sweep_ref(fd, wj, dr)[1]
        fr = (nd < dr).astype(jnp.int8)
        dr = nd
        if bool(jnp.any(fr != 0)):
            prod_r += 1
        else:
            stop_r = True
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(fr))
    assert int(prod_k) == prod_r and bool(stop_k) == stop_r


def test_fused_counting_multisweep_matches_per_sweep():
    """Counting fused block == iterated per-sweep counting kernel: the
    (dist, sigma) pair stays resident and path counts stay exact."""
    rng = np.random.default_rng(23)
    n, s = 256, 64
    adj = jnp.asarray((rng.random((n, n)) < 0.03).astype(np.int8))
    dist = np.full((s, n), -1, np.int32)
    dist[np.arange(s), np.arange(s)] = 0
    sigma = (dist == 0).astype(np.float32)
    f = (dist == 0).astype(np.int8)
    d, sg, fr = jnp.asarray(dist), jnp.asarray(sigma), jnp.asarray(f)
    new_k, (dist_k, sig_k), prod_k, stop_k = fused_counting_multisweep(
        fr, adj, (d, sg), 0, 6, bs=64, max_sweeps=6, interpret=True)
    prod_r, stop_r = 0, False
    new_r = jnp.zeros_like(fr)
    for t in range(6):
        if stop_r:
            break
        fs = jnp.where(fr != 0, sg, 0.0)
        new_r, d, sg = fused_counting_sweep(fs, adj, d, sg, t + 1, bs=64,
                                            interpret=True)
        fr = new_r
        if bool(jnp.any(new_r != 0)):
            prod_r += 1
        else:
            stop_r = True
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(d))
    np.testing.assert_array_equal(np.asarray(sig_k), np.asarray(sg))
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    assert int(prod_k) == prod_r and bool(stop_k) == stop_r


# --------------------------------------------------------------------------
# structural guard: the boolean kernel push must not lower an f32 GEMM
# --------------------------------------------------------------------------

def _boolean_push_jaxpr(n=256, s=64):
    import repro.core.sweep as S
    adj_pull = jnp.zeros((n, n // 32), jnp.uint32)
    push = S.boolean_forms(jnp.zeros((1, 1), jnp.int8), adj_pull, None,
                           n_pad=n, s=s, use_kernel=True,
                           interpret=True)[S.PUSH]
    f = jnp.zeros((s, n), jnp.int8)
    d = jnp.zeros((s, n), jnp.int32)
    p = jnp.zeros((s, n), jnp.int32)
    return str(jax.make_jaxpr(push)(f, d, p, jnp.int32(1)))


def test_boolean_kernel_push_has_no_f32_dot():
    """Bit-packing is structural, not incidental: the boolean kernel
    push (and the fused boolean block) must trace to a jaxpr with NO
    dot_general anywhere — dense boolean push no longer pays f32 GEMM
    cost (paper Eq. 13: 32 adjacency lanes per uint32 word)."""
    assert "dot_general" not in _boolean_push_jaxpr()
    n, s = 256, 64
    fused_jaxpr = str(jax.make_jaxpr(
        lambda f, ap, d: fused_boolean_multisweep(
            f, ap, d, 0, 4, bs=64, max_sweeps=4, interpret=True))(
        jnp.zeros((s, n), jnp.int8), jnp.zeros((n, n // 32), jnp.uint32),
        jnp.zeros((s, n), jnp.int32)))
    assert "dot_general" not in fused_jaxpr


def test_no_f32_dot_guard_sees_nested_jaxprs():
    """Positive controls for the guard above: (a) the XLA reference push
    DOES contain dot_general, and (b) a dot inside a pallas_call kernel
    (the counting fused block, interpret mode) IS visible to the same
    str(make_jaxpr(...)) probe — so the boolean assertion cannot pass
    vacuously by the dot hiding below the traced surface."""
    import repro.core.sweep as S
    n, s = 256, 64
    adj = jnp.zeros((n, n), jnp.int8)
    ref_push = S.boolean_forms(adj, jnp.zeros((1, 1), jnp.uint32), None,
                               n_pad=n, s=s, use_kernel=False,
                               interpret=True)[S.PUSH]
    f = jnp.zeros((s, n), jnp.int8)
    d = jnp.zeros((s, n), jnp.int32)
    p = jnp.zeros((s, n), jnp.int32)
    assert "dot_general" in str(jax.make_jaxpr(ref_push)(f, d, p,
                                                         jnp.int32(1)))
    counting_jaxpr = str(jax.make_jaxpr(
        lambda f8, a, dd, sgg: fused_counting_multisweep(
            f8, a, (dd, sgg), 0, 2, bs=64, max_sweeps=2, interpret=True))(
        jnp.zeros((s, n), jnp.int8), adj, d,
        jnp.zeros((s, n), jnp.float32)))
    assert "dot_general" in counting_jaxpr


def _fused_sweep_vs_ref(seed, density, visited):
    """kernel == oracle for arbitrary frontier/visited states."""
    rng = np.random.default_rng(seed)
    n, s = 256, 64
    adj = jnp.asarray((rng.random((n, n)) < 0.02).astype(np.int8))
    f = jnp.asarray((rng.random((s, n)) < density).astype(np.int8))
    dist = jnp.asarray(
        np.where(rng.random((s, n)) < visited, 2, -1).astype(np.int32))
    new_k, dist_k = fused_sweep(f, adj, dist, 7, bs=64, bn=128, bk=128,
                                interpret=True)
    new_r, dist_r = sweep_ref(f, adj, dist, 7)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


@pytest.mark.parametrize("seed", range(8))
def test_fused_sweep_randomized(seed):
    """Seeded always-run slice of the property space (the hypothesis
    variant below explores it adaptively when hypothesis is installed)."""
    rng = np.random.default_rng(seed * 7919 + 13)
    _fused_sweep_vs_ref(int(rng.integers(0, 10_000)),
                        float(rng.uniform(0.0, 0.3)),
                        float(rng.uniform(0.0, 1.0)))


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), density=st.floats(0.0, 0.3),
           visited=st.floats(0.0, 1.0))
    def test_fused_sweep_property(seed, density, visited):
        _fused_sweep_vs_ref(seed, density, visited)


def test_msbfs_kernel_end_to_end():
    g = gen.rmat(8, 5, directed=False, seed=21)
    n = 256
    adj = jnp.asarray(np.asarray(g.to_dense_padded(n)), jnp.int8)
    srcs = jnp.arange(64, dtype=jnp.int32)
    res = msbfs_kernel(adj, srcs, max_steps=n, interpret=True,
                       bs=64, bn=128, bk=128)
    refs = bfs_dists(g, np.asarray(srcs))
    np.testing.assert_array_equal(
        np.asarray(res.dist)[:, :g.n_nodes], refs)


def test_msbfs_packed_end_to_end():
    g = gen.rmat(8, 5, directed=True, seed=22)
    n = 256
    adj = jnp.asarray(np.asarray(g.to_dense_padded(n)), jnp.int8)
    ap = pack_adjacency_pull(adj)
    srcs = jnp.arange(16, dtype=jnp.int32)
    res = msbfs_packed(ap, srcs, n, max_steps=n, interpret=True,
                       bs=8, bn=128, wk=8)
    refs = bfs_dists(g, np.asarray(srcs))
    np.testing.assert_array_equal(
        np.asarray(res.dist)[:, :g.n_nodes], refs)


def test_tile_skip_preserves_semantics():
    """All-visited output tiles and empty frontier tiles must not change
    results (the Thm 3.2 tile-skip)."""
    rng = np.random.default_rng(0)
    n, s = 256, 64
    adj = jnp.asarray((rng.random((n, n)) < 0.05).astype(np.int8))
    f = np.zeros((s, n), np.int8)
    f[:, :128] = (rng.random((s, 128)) < 0.1)   # half the k-tiles empty
    dist = np.full((s, n), -1, np.int32)
    dist[:, 128:] = 3                            # half the out-tiles visited
    new_k, dist_k = fused_sweep(jnp.asarray(f), adj, jnp.asarray(dist), 4,
                                bs=64, bn=128, bk=128, interpret=True)
    new_r, dist_r = sweep_ref(jnp.asarray(f), adj, jnp.asarray(dist), 4)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


# --------------------------------------------------------------------------
# tropical semiring kernels (paper §5, min-plus)
# --------------------------------------------------------------------------

def _random_tropical_state(rng, s, n, *, density=0.03, wdensity=0.03):
    w = np.full((n, n), np.inf, np.float32)
    mask = rng.random((n, n)) < wdensity
    w[mask] = rng.uniform(0.5, 4.0, mask.sum())
    dist = np.where(rng.random((s, n)) < 0.3,
                    rng.uniform(0.0, 10.0, (s, n)), np.inf).astype(np.float32)
    f = (rng.random((s, n)) < density).astype(np.int8)
    fdist = np.where(f != 0, dist, np.inf).astype(np.float32)
    finite = w[np.isfinite(w)]
    w_min = np.float32(finite.min() if finite.size else np.inf)
    return (jnp.asarray(f), jnp.asarray(fdist), jnp.asarray(w),
            jnp.asarray(dist), w_min)


@pytest.mark.parametrize("s,n,bs,bn,bk", [
    (64, 256, 64, 128, 128),
    (8, 128, 8, 128, 128),
    (16, 384, 16, 128, 128),
])
def test_minplus_sweep_shapes(s, n, bs, bn, bk):
    rng = np.random.default_rng(s * n + 1)
    _, fdist, w, dist, w_min = _random_tropical_state(rng, s, n)
    new_k, dist_k = fused_minplus_sweep(fdist, w, dist, w_min, bs=bs, bn=bn,
                                        bk=bk, interpret=True)
    new_r, dist_r = minplus_sweep_ref(fdist, w, dist)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


def test_minplus_settled_skip_preserves_semantics():
    """The tropical o_occ table (Dijkstra settled bound at tile rank) must
    be exact: tiles whose distances all sit under min_frontier + w_min are
    skipped, and the result still matches the unskipped oracle."""
    rng = np.random.default_rng(7)
    s, n = 64, 256
    w = np.full((n, n), np.inf, np.float32)
    mask = rng.random((n, n)) < 0.05
    w[mask] = rng.uniform(1.0, 2.0, mask.sum())
    dist = np.full((s, n), np.inf, np.float32)
    dist[:, :128] = rng.uniform(0.0, 0.5, (s, 128))    # settled out-tile
    f = np.zeros((s, n), np.int8)
    f[:, :64] = (rng.random((s, 64)) < 0.2)            # half the k-tiles dead
    fdist = np.where(f != 0, dist, np.inf).astype(np.float32)
    w_min = np.float32(w[np.isfinite(w)].min())
    new_k, dist_k = fused_minplus_sweep(
        jnp.asarray(fdist), jnp.asarray(w), jnp.asarray(dist), w_min,
        bs=64, bn=128, bk=128, interpret=True)
    new_r, dist_r = minplus_sweep_ref(jnp.asarray(fdist), jnp.asarray(w),
                                      jnp.asarray(dist))
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


@pytest.mark.parametrize("s,n_pad,eb", [(8, 128, 128), (16, 256, 128),
                                        (32, 256, 256)])
def test_sparse_relax_shapes(s, n_pad, eb):
    rng = np.random.default_rng(s + n_pad)
    n = n_pad - 1                                     # room for the sentinel
    m = 4 * n
    m_pad = ((m + eb - 1) // eb) * eb
    src = np.full(m_pad, n, np.int32)
    dst = np.full(m_pad, n, np.int32)
    w = np.full(m_pad, np.inf, np.float32)
    src[:m] = rng.integers(0, n, m)
    dst[:m] = rng.integers(0, n, m)
    w[:m] = rng.uniform(0.5, 4.0, m)
    f = (rng.random((s, n_pad)) < 0.1).astype(np.int8)
    dist = np.where(rng.random((s, n_pad)) < 0.4,
                    rng.uniform(0.0, 8.0, (s, n_pad)),
                    np.inf).astype(np.float32)
    args = (jnp.asarray(f), jnp.asarray(dist), jnp.asarray(src),
            jnp.asarray(dst), jnp.asarray(w))
    new_k, dist_k = sparse_relax_sweep(*args, eb=eb, interpret=True)
    new_r, dist_r = sparse_relax_ref(*args)
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))
    np.testing.assert_array_equal(np.asarray(dist_k), np.asarray(dist_r))


# --------------------------------------------------------------------------
# counting semiring kernel (Brandes stage 1 — path counting)
# --------------------------------------------------------------------------

def _random_counting_state(rng, s, n, *, density=0.05, visited=0.3):
    adj = (rng.random((n, n)) < 0.03).astype(np.int8)
    dist = np.where(rng.random((s, n)) < visited, 2, -1).astype(np.int32)
    sigma = np.where(dist >= 0, rng.integers(1, 9, (s, n)), 0
                     ).astype(np.float32)
    f = ((rng.random((s, n)) < density) & (dist >= 0)).astype(np.int8)
    fsigma = np.where(f != 0, sigma, 0.0).astype(np.float32)
    return (jnp.asarray(fsigma), jnp.asarray(adj), jnp.asarray(dist),
            jnp.asarray(sigma))


@pytest.mark.parametrize("s,n,bs,bn,bk", [
    (64, 256, 64, 128, 128),
    (8, 128, 8, 128, 128),
    (16, 384, 16, 128, 128),
])
def test_counting_sweep_shapes(s, n, bs, bn, bk):
    rng = np.random.default_rng(s * n + 3)
    fsigma, adj, dist, sigma = _random_counting_state(rng, s, n)
    k_out = fused_counting_sweep(fsigma, adj, dist, sigma, 5, bs=bs, bn=bn,
                                 bk=bk, interpret=True)
    r_out = counting_sweep_ref(fsigma, adj, dist, sigma, 5)
    for got, ref in zip(k_out, r_out):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def _counting_sweep_vs_ref(seed, density, visited):
    rng = np.random.default_rng(seed)
    fsigma, adj, dist, sigma = _random_counting_state(
        rng, 64, 256, density=density, visited=visited)
    k_out = fused_counting_sweep(fsigma, adj, dist, sigma, 7, bs=64,
                                 bn=128, bk=128, interpret=True)
    r_out = counting_sweep_ref(fsigma, adj, dist, sigma, 7)
    for got, ref in zip(k_out, r_out):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("seed", range(6))
def test_counting_sweep_randomized(seed):
    rng = np.random.default_rng(seed * 6199 + 29)
    _counting_sweep_vs_ref(int(rng.integers(0, 10_000)),
                           float(rng.uniform(0.0, 0.3)),
                           float(rng.uniform(0.0, 1.0)))


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), density=st.floats(0.0, 0.3),
           visited=st.floats(0.0, 1.0))
    def test_counting_sweep_property(seed, density, visited):
        _counting_sweep_vs_ref(seed, density, visited)


def test_counting_rectangular_partials_sum_to_square():
    """K-row block partials combine with the masked-add ⊕ (sum of gated
    candidates) to the square sweep — the sharded executor's reduction.
    Path counts are integers in f32, so the sum is exact."""
    rng = np.random.default_rng(19)
    s, n, k = 8, 256, 128
    fsigma, adj, dist, sigma = _random_counting_state(rng, s, n)
    new_sq, dist_sq, sig_sq = fused_counting_sweep(
        fsigma, adj, dist, sigma, 5, bs=8, bn=128, bk=128, interpret=True)
    cand = np.zeros((s, n), np.float32)
    for k0 in range(0, n, k):
        new_p, _, nsg_p = fused_counting_sweep(
            fsigma[:, k0: k0 + k], adj[k0: k0 + k], dist, sigma, 5,
            bs=8, bn=128, bk=128, interpret=True)
        cand += np.where(np.asarray(new_p) != 0, np.asarray(nsg_p), 0.0)
    new = (cand > 0) & (np.asarray(dist) < 0)
    np.testing.assert_array_equal(new.astype(np.int8), np.asarray(new_sq))
    np.testing.assert_array_equal(
        np.where(new, 5, np.asarray(dist)), np.asarray(dist_sq))
    np.testing.assert_array_equal(
        np.where(new, cand, np.asarray(sigma)), np.asarray(sig_sq))


def test_counting_tile_skip_preserves_semantics():
    """Dead frontier k-tiles and all-visited output tiles must not
    change either half of the (dist, sigma) state — the boolean o_occ
    is sound for the counting semiring (sigma only moves with dist)."""
    rng = np.random.default_rng(23)
    s, n = 64, 256
    adj = (rng.random((n, n)) < 0.05).astype(np.int8)
    dist = np.full((s, n), -1, np.int32)
    dist[:, 128:] = 3                            # half the out-tiles visited
    sigma = np.where(dist >= 0, 2.0, 0.0).astype(np.float32)
    f = np.zeros((s, n), np.int8)
    f[:, 128: 192] = (rng.random((s, 64)) < 0.2)  # half the k-tiles empty
    fsigma = np.where(f != 0, sigma, 0.0).astype(np.float32)
    args = (jnp.asarray(fsigma), jnp.asarray(adj), jnp.asarray(dist),
            jnp.asarray(sigma))
    k_out = fused_counting_sweep(*args, 4, bs=64, bn=128, bk=128,
                                 interpret=True)
    r_out = counting_sweep_ref(*args, 4)
    for got, ref in zip(k_out, r_out):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# --------------------------------------------------------------------------
# cross-semiring kernel equivalence (acceptance)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["dense", "sparse", "auto"])
def test_weighted_kernel_path_matches_dijkstra(mode, random_weighted):
    """weighted_apsp dispatching the tropical Pallas kernels under
    interpret=True == scipy Dijkstra (the PR's acceptance criterion)."""
    g, w = random_weighted(100, 3.0, 41)
    sources = np.arange(12, dtype=np.int32)
    ref = dijkstra_dists(g, w, sources)
    res = weighted_apsp(g, w, sources,
                        config=WeightedConfig(mode=mode, source_batch=16,
                                              use_kernel=True))
    np.testing.assert_allclose(np.asarray(res.dist), ref, rtol=1e-5)
    assert int(res.direction_counts.sum()) == int(res.sweeps) > 0


def test_weighted_kernel_matches_reference_forms(random_weighted):
    """Kernel forms and XLA reference forms are the same sweeps: identical
    distances AND identical sweep counts on the same graph."""
    g, w = random_weighted(90, 4.0, 43)
    sources = np.arange(8, dtype=np.int32)
    for mode in ("dense", "sparse"):
        kern = weighted_apsp(g, w, sources,
                             config=WeightedConfig(mode=mode, source_batch=8,
                                                   use_kernel=True))
        ref = weighted_apsp(g, w, sources,
                            config=WeightedConfig(mode=mode, source_batch=8,
                                                  use_kernel=False))
        np.testing.assert_array_equal(np.asarray(kern.dist),
                                      np.asarray(ref.dist))
        assert int(kern.sweeps) == int(ref.sweeps)


def test_unit_weight_tropical_kernel_equals_boolean_kernel():
    """(min,+) with unit weights through the tropical kernel == boolean
    BFS through the boolean kernel — the cross-semiring contract at the
    kernel layer."""
    g = gen.rmat(8, 5, directed=False, seed=51)
    n_pad = g.n_padded(128)
    w = jnp.ones((g.m_pad,), jnp.float32)
    sources = np.arange(16, dtype=np.int32)
    trop = weighted_apsp(g, np.asarray(w), sources,
                         config=WeightedConfig(mode="dense", source_batch=16,
                                               use_kernel=True))
    adj = jnp.asarray(np.asarray(g.to_dense_padded(n_pad)), jnp.int8)
    boolean = msbfs_kernel(adj, jnp.asarray(sources), max_steps=n_pad,
                           interpret=True, bs=16, bn=128, bk=128)
    bdist = np.asarray(boolean.dist)[:, :g.n_nodes].astype(np.float64)
    bdist = np.where(bdist < 0, np.inf, bdist)
    np.testing.assert_allclose(np.asarray(trop.dist), bdist)


# --------------------------------------------------------------------------
# interpret-only policy: the registry seam must keep the tropical sparse
# kernel off compiled (real-TPU) backends
# --------------------------------------------------------------------------

def test_tropical_sparse_is_marked_interpret_only():
    ks = registry.get("tropical")
    assert "sparse" in ks.interpret_only
    assert ks.dispatchable("sparse", interpret=True)
    assert not ks.dispatchable("sparse", interpret=False)
    assert ks.dispatchable("dense", interpret=False)
    assert registry.get("boolean").dispatchable("push", interpret=False)


def test_sparse_relax_sweep_refuses_compiled_dispatch():
    """The kernel wrapper itself hard-errors on interpret=False — the
    contract is not just a registry convention."""
    f = jnp.zeros((8, 128), jnp.int8)
    d = jnp.full((8, 128), jnp.inf, jnp.float32)
    idx = jnp.full((128,), 127, jnp.int32)
    w = jnp.full((128,), jnp.inf, jnp.float32)
    with pytest.raises(RuntimeError, match="interpret-only"):
        sparse_relax_sweep(f, d, idx, idx, w, eb=128, interpret=False)


def test_compiled_tropical_dispatch_falls_back_to_xla_sparse():
    """sweep.tropical_forms(use_kernel=True, interpret=False) must route
    the sparse form to XLA: poison the registry's sparse kernel and check
    the returned closure never calls it yet still relaxes correctly."""
    import repro.core.sweep as S
    ks = registry.get("tropical")

    def boom(*a, **k):
        raise AssertionError("sparse kernel dispatched on compiled path")

    registry.register(registry.KernelSet(
        semiring="tropical", forms={**ks.forms, "sparse": boom},
        vmem_bytes=ks.vmem_bytes, notes=ks.notes,
        interpret_only=ks.interpret_only))
    try:
        g = gen.erdos_renyi(100, 3.0, seed=7)
        rng = np.random.default_rng(0)
        w = jnp.asarray(np.where(np.arange(g.m_pad) < g.n_edges,
                                 rng.uniform(0.5, 4.0, g.m_pad),
                                 np.inf).astype(np.float32))
        _, sparse = S.tropical_forms(None, g.src, g.dst, w,
                                     use_kernel=True, interpret=False)
        n_pad = g.n_padded(128)
        f = jnp.zeros((4, n_pad), jnp.int8).at[:, 0].set(1)
        d = jnp.full((4, n_pad), jnp.inf).at[:, 0].set(0.0)
        new, nd, _ = sparse(f, d, jnp.zeros((1,), jnp.int32), jnp.int32(1))
        _, ref_sparse = S.tropical_forms(None, g.src, g.dst, w,
                                         use_kernel=False)
        new_r, nd_r, _ = ref_sparse(f, d, jnp.zeros((1,), jnp.int32),
                                    jnp.int32(1))
        np.testing.assert_array_equal(np.asarray(new), np.asarray(new_r))
        np.testing.assert_array_equal(np.asarray(nd), np.asarray(nd_r))
    finally:
        registry.register(ks)    # restore the real kernel set


# --------------------------------------------------------------------------
# rectangular (K-row block) kernel dispatch — the sharded executor's
# vertex-sharded partial sweeps
# --------------------------------------------------------------------------

@pytest.mark.parametrize("s,k,n", [(64, 128, 256), (8, 128, 384)])
def test_fused_sweep_rectangular_matches_square_slice(s, k, n):
    """fused_sweep on a (k, n) K-row block == the k-rows' contribution:
    OR of the C block partials must equal the square sweep."""
    rng = np.random.default_rng(s + k + n)
    adj = jnp.asarray((rng.random((n, n)) < 0.04).astype(np.int8))
    f, dist = _random_state(rng, s, n)
    new_sq, dist_sq = fused_sweep(f, adj, dist, 5, bs=min(s, 64), bn=128,
                                  bk=128, interpret=True)
    parts = []
    for k0 in range(0, n, k):
        new_p, _ = fused_sweep(f[:, k0: k0 + k], adj[k0: k0 + k], dist, 5,
                               bs=min(s, 64), bn=128, bk=128,
                               interpret=True)
        parts.append(np.asarray(new_p))
    new_or = np.maximum.reduce(parts)
    np.testing.assert_array_equal(new_or, np.asarray(new_sq))
    dist_comb = np.where(new_or != 0, 5, np.asarray(dist))
    np.testing.assert_array_equal(dist_comb, np.asarray(dist_sq))


def test_minplus_rectangular_matches_square_slice():
    """fused_minplus_sweep K-row partials min-combine to the square
    result (⊕ = min is exact in f32)."""
    rng = np.random.default_rng(11)
    s, n, k = 8, 256, 128
    _, fdist, w, dist, w_min = _random_tropical_state(rng, s, n)
    _, dist_sq = fused_minplus_sweep(fdist, w, dist, w_min, bs=8, bn=128,
                                     bk=128, interpret=True)
    parts = []
    for k0 in range(0, n, k):
        _, nd_p = fused_minplus_sweep(fdist[:, k0: k0 + k],
                                      w[k0: k0 + k], dist, w_min, bs=8,
                                      bn=128, bk=128, interpret=True)
        parts.append(np.asarray(nd_p))
    np.testing.assert_array_equal(np.minimum.reduce(parts),
                                  np.asarray(dist_sq))
