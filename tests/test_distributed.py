"""Distribution tests on virtual devices (subprocess: jax must initialize
with --xla_force_host_platform_device_count before first use)."""
import os
import subprocess
import sys
import textwrap

import pytest


def _run(body: str, devices: int = 8):
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \\
            "--xla_force_host_platform_device_count={devices}"
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=900,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-4000:]
    return res.stdout


@pytest.mark.slow
def test_sharded_apsp_boolean_bit_identical_to_single_device():
    """Acceptance: sharded boolean APSP on an 8-virtual-device CPU mesh
    returns bit-identical distances AND sweep counts vs the single-device
    engine, across source-only and source×vertex meshes and all three
    sweep modes — and matches the independent queue-BFS oracle."""
    out = _run("""
        import sys; sys.path.insert(0, "tests")
        import numpy as np, jax
        from oracles import bfs_dists
        from repro.graph import generators as gen
        from repro.core import (EngineConfig, ShardedConfig, apsp_engine,
                                sharded_apsp)
        from repro.launch.mesh import make_mesh
        g = gen.rmat(9, 6, directed=False, seed=5)       # n = 512
        sources = np.arange(24, dtype=np.int32)
        single = apsp_engine(g, sources,
                             config=EngineConfig(mode="push",
                                                 source_batch=24))
        np.testing.assert_array_equal(np.asarray(single.dist),
                                      bfs_dists(g, sources))
        for shape, axes in [((8,), ("data",)),
                            ((2, 4), ("data", "model")),
                            ((4, 2), ("data", "model"))]:
            mesh = make_mesh(shape, axes)
            for mode in ("dense", "sparse", "auto"):
                res = sharded_apsp(g, sources, mesh=mesh,
                                   config=ShardedConfig(mode=mode))
                np.testing.assert_array_equal(np.asarray(res.dist),
                                              np.asarray(single.dist))
                assert int(res.sweeps) == int(single.sweeps), (shape, mode)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_sparse_form_packs_its_local_table():
    """The sharded executor's boolean sparse form applies
    ``sweep.PACK_GATHER_BYTES`` to its local shapes: with the limit at 0
    each shard gathers its 32 keys packed to one word, on a source-only
    and a source x vertex mesh, and the distances stay the plain BFS's."""
    out = _run("""
        import sys; sys.path.insert(0, "tests")
        import numpy as np
        from oracles import bfs_dists
        from repro.graph import generators as gen
        from repro.core import ShardedConfig, sharded_apsp
        from repro.core import sweep as S
        from repro.launch.mesh import make_mesh
        S.PACK_GATHER_BYTES = 0
        words = []
        packed = S.packed_row_or
        S.packed_row_or = lambda f, row_src, w: (
            words.append((f.shape, w)) or packed(f, row_src, w))
        g = gen.rmat(8, 8, directed=False, seed=3)
        sources = np.arange(64, dtype=np.int32)
        for shape, axes in [((2,), ("data",)), ((2, 2), ("data", "model"))]:
            res = sharded_apsp(g, sources, mesh=make_mesh(shape, axes),
                               config=ShardedConfig(mode="sparse"))
            np.testing.assert_array_equal(np.asarray(res.dist),
                                          bfs_dists(g, sources))
        assert words and all(s[0] == 32 and w == 1 for s, w in words), words
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_apsp_tropical_bit_identical_to_single_device():
    """Same acceptance for the tropical semiring: (min,+) APSP sharded
    over sources and vertices is bit-identical (f32 min is exact) to
    weighted_apsp and allclose to scipy Dijkstra."""
    out = _run("""
        import sys; sys.path.insert(0, "tests")
        import numpy as np, jax
        from oracles import dijkstra_dists
        from repro.graph import generators as gen
        from repro.core import (ShardedConfig, WeightedConfig,
                                sharded_apsp, weighted_apsp)
        from repro.launch.mesh import make_mesh
        g = gen.rmat(9, 6, directed=False, seed=5)
        w = np.random.default_rng(0).uniform(0.5, 4.0, g.m_pad).astype(
            np.float32)
        sources = np.arange(24, dtype=np.int32)
        single = weighted_apsp(g, w, sources,
                               config=WeightedConfig(mode="dense",
                                                     source_batch=24))
        np.testing.assert_allclose(np.asarray(single.dist),
                                   dijkstra_dists(g, w, sources),
                                   rtol=1e-5)
        for shape, axes in [((8,), ("data",)),
                            ((2, 4), ("data", "model"))]:
            mesh = make_mesh(shape, axes)
            for mode in ("dense", "sparse", "auto"):
                res = sharded_apsp(g, sources, mesh=mesh, weights=w,
                                   config=ShardedConfig(
                                       semiring="tropical", mode=mode))
                np.testing.assert_array_equal(np.asarray(res.dist),
                                              np.asarray(single.dist))
                assert int(res.sweeps) == int(single.sweeps), (shape, mode)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_apsp_non_divisible_padding():
    """n=237 doesn't divide the 4-way vertex shard and S=13 doesn't
    divide the 2-way source shard: the executor's padding must keep both
    semirings bit-identical to the single-device engines."""
    out = _run("""
        import sys; sys.path.insert(0, "tests")
        import numpy as np, jax
        from repro.graph import generators as gen
        from repro.core import (EngineConfig, ShardedConfig,
                                WeightedConfig, apsp_engine, sharded_apsp,
                                weighted_apsp)
        from repro.launch.mesh import make_mesh
        g = gen.erdos_renyi(237, 3.0, seed=9)
        sources = np.arange(13, dtype=np.int32)
        mesh = make_mesh((2, 4), ("data", "model"))
        single = apsp_engine(g, sources,
                             config=EngineConfig(mode="sparse",
                                                 source_batch=16))
        for mode in ("dense", "sparse"):
            res = sharded_apsp(g, sources, mesh=mesh,
                               config=ShardedConfig(mode=mode))
            np.testing.assert_array_equal(np.asarray(res.dist),
                                          np.asarray(single.dist))
            assert int(res.sweeps) == int(single.sweeps), mode
        w = np.random.default_rng(1).uniform(0.1, 5.0, g.m_pad).astype(
            np.float32)
        wsingle = weighted_apsp(g, w, sources,
                                config=WeightedConfig(mode="sparse",
                                                      source_batch=16))
        for mode in ("dense", "sparse"):
            res = sharded_apsp(g, sources, mesh=mesh, weights=w,
                               config=ShardedConfig(semiring="tropical",
                                                    mode=mode))
            np.testing.assert_array_equal(np.asarray(res.dist),
                                          np.asarray(wsingle.dist))
            assert int(res.sweeps) == int(wsingle.sweeps), mode
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_counting_bit_identical_and_betweenness_matches_oracle():
    """Acceptance: the counting semiring (non-idempotent ⊕ — sigma
    partials combine with the masked-add psum) is bit-identical to the
    single-device counting engine on an 8-virtual-device mesh across
    source-only and source×vertex shardings and all modes, including
    the rectangular kernel path — and the mesh-routed betweenness
    matches the independent NumPy Brandes oracle."""
    out = _run("""
        import sys; sys.path.insert(0, "tests")
        import numpy as np, jax
        from oracles import bfs_sigmas, brandes_betweenness
        from repro.graph import generators as gen
        from repro.core import (CentralityConfig, ShardedConfig,
                                betweenness, counting_apsp, sharded_apsp)
        from repro.launch.mesh import make_mesh
        g = gen.rmat(8, 5, directed=False, seed=5)       # n = 256
        sources = np.arange(24, dtype=np.int32)
        single = counting_apsp(g, sources,
                               config=CentralityConfig(mode="push",
                                                       source_batch=24))
        np.testing.assert_allclose(np.asarray(single.sigma),
                                   bfs_sigmas(g, sources))
        for shape, axes in [((8,), ("data",)),
                            ((2, 4), ("data", "model")),
                            ((4, 2), ("data", "model"))]:
            mesh = make_mesh(shape, axes)
            for mode in ("dense", "sparse", "auto"):
                res = sharded_apsp(g, sources, mesh=mesh,
                                   config=ShardedConfig(
                                       semiring="counting", mode=mode))
                np.testing.assert_array_equal(np.asarray(res.dist),
                                              np.asarray(single.dist))
                np.testing.assert_array_equal(np.asarray(res.sigma),
                                              np.asarray(single.sigma))
                assert int(res.sweeps) == int(single.sweeps), (shape, mode)
        # rectangular counting kernel through the registry (interpret)
        mesh = make_mesh((2, 4), ("data", "model"))
        res = sharded_apsp(g, sources, mesh=mesh,
                           config=ShardedConfig(semiring="counting",
                                                mode="dense",
                                                use_kernel=True))
        np.testing.assert_array_equal(np.asarray(res.sigma),
                                      np.asarray(single.sigma))
        # end-to-end: betweenness through the sharded forward pass
        bc = betweenness(g, mesh=make_mesh((8,), ("data",)))
        np.testing.assert_allclose(bc, brandes_betweenness(g),
                                   rtol=1e-4, atol=1e-6)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_kernel_path_rides_the_executor():
    """use_kernel=True dispatches the rectangular Pallas kernels through
    the registry inside the sharded executor (interpret mode on CPU)."""
    out = _run("""
        import numpy as np, jax
        from repro.graph import generators as gen
        from repro.core import (EngineConfig, ShardedConfig,
                                WeightedConfig, apsp_engine, sharded_apsp,
                                weighted_apsp)
        from repro.launch.mesh import make_mesh
        g = gen.rmat(7, 4, directed=False, seed=3)       # n = 128
        sources = np.arange(8, dtype=np.int32)
        mesh = make_mesh((2, 2), ("data", "model"))
        single = apsp_engine(g, sources,
                             config=EngineConfig(mode="push",
                                                 source_batch=8))
        res = sharded_apsp(g, sources, mesh=mesh,
                           config=ShardedConfig(mode="dense",
                                                use_kernel=True))
        np.testing.assert_array_equal(np.asarray(res.dist),
                                      np.asarray(single.dist))
        assert int(res.sweeps) == int(single.sweeps)
        w = np.random.default_rng(0).uniform(0.5, 4.0, g.m_pad).astype(
            np.float32)
        wsingle = weighted_apsp(g, w, sources,
                                config=WeightedConfig(mode="dense",
                                                      source_batch=8))
        res = sharded_apsp(g, sources, mesh=mesh, weights=w,
                           config=ShardedConfig(semiring="tropical",
                                                mode="dense",
                                                use_kernel=True))
        np.testing.assert_array_equal(np.asarray(res.dist),
                                      np.asarray(wsingle.dist))
        assert int(res.sweeps) == int(wsingle.sweeps)
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_lm_train_step_matches_single_device():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro._attic.models import transformer as T
        from repro.train import optimizer as O
        from repro.train.train_loop import make_train_step
        from repro._attic.launch.cells import shardings

        cfg = T.LMConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                         n_kv=2, d_head=16, d_ff=128, vocab=256,
                         dtype=jnp.float32)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        opt = O.sgd(lr=0.1)
        state = opt.init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
        batch = {"tokens": toks, "labels": toks}
        step = make_train_step(lambda p, b: T.loss_fn(p, b, cfg), opt)

        p1, _, m1 = jax.jit(step)(params, state, batch)

        pspec = T.param_specs(cfg)
        sspec = opt.state_specs(pspec)
        bspec = {"tokens": P("data", None), "labels": P("data", None)}
        with jax.sharding.set_mesh(mesh):
            jstep = jax.jit(step,
                            in_shardings=shardings(mesh, (pspec, sspec,
                                                          bspec)),
                            out_shardings=shardings(mesh, (pspec, sspec,
                                                           None)))
            p2, _, m2 = jstep(params, state, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
        for a, b in zip(jax.tree_util.tree_leaves(p1),
                        jax.tree_util.tree_leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-2, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_embed_lookup_sharded_equals_local():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro._attic.models.layers import embed_lookup
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        table = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, 64)
        ref = table[toks]
        with jax.sharding.set_mesh(mesh):
            t = jax.device_put(table, NamedSharding(mesh, P(None, "model")))
            k = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
            got = jax.jit(lambda a, b: embed_lookup(a, b, jnp.float32))(t, k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_compressed_cross_pod_psum():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.train.compression import make_cross_pod_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        psum_c = make_cross_pod_psum("int8")
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 0.1

        def f(v):
            return psum_c(v)

        got = jax.shard_map(f, mesh=mesh,
                            in_specs=jax.sharding.PartitionSpec("pod"),
                            out_specs=jax.sharding.PartitionSpec("pod"))(x)
        ref = jnp.broadcast_to(x.sum(0, keepdims=True), x.shape)
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 0.01, err
        print("OK")
    """, devices=4)
    assert "OK" in out
