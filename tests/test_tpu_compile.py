"""Compile the chip path for a TPU v5e that is described, not attached.

Interpret mode (what every other kernel test runs) accepts shapes and
primitives that Mosaic refuses: a dynamic slice along lanes, a block
whose last dim is neither a multiple of 128 nor the whole width, a
scalar stored to VMEM, more scoped VMEM than the kernel may use.  These
tests compile each kernel the TPU path dispatches, at the widths
``chip_smoke.py`` runs, plus the Graph500 sparse batch program at scales
20 and 21, whose device memory must fit one 16 GB chip.  Nothing runs; a compile
that passes here is not a chip run.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and pytest-xdist workers import
every test file.
"""
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig, _run_batch
from repro.core.sweep import SPARSE, row_count, row_width
from repro.kernels import common
from repro.kernels.bovm import (fused_boolean_multisweep, packed_pull_sweep,
                                packed_push_sweep)
from repro.kernels.counting import (fused_counting_multisweep,
                                    fused_counting_sweep)
from repro.kernels.tropical import (fused_minplus_multisweep,
                                    fused_minplus_sweep)

# n_pad = round_up(n + 1, 128) of the graphs chip_smoke.py runs
GRID_ROAD_MD = 32512       # SUITE grid_road_md, 180 x 180 = 32,400 vertices
RMAT_SOCIAL_MD = 8320      # SUITE rmat_social_md, 2^13 vertices
GRID_ROAD_SM = 4224        # SUITE grid_road_sm, 64 x 64: the fused phase
FUSED_DENSE = 1152         # largest f32/int8 whole-operand fused width used
S = 128                    # sources per tile in the kernel phase

# Graph500 scale 20, edge factor 16, undirected: 2^25 lanes before dedup
SCALE20_N = 1 << 20
SCALE20_LANES = 1 << 25
SCALE20_KEYS = 64
SCALE21_LANES = 61 << 20   # bench/configs/graph500_s21.json's m_pad
V5E_HBM_BYTES = 15.75 * 2 ** 30      # what the v5e compiler may allocate


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be cached but never read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n_pad", [GRID_ROAD_MD, RMAT_SOCIAL_MD])
@pytest.mark.parametrize("direction", ["push", "pull"])
def test_packed_kernels_compile(one_chip, n_pad, direction):
    """The engine's packed push (bs = 128) and pull (bs = 8) sweeps at
    the word tile ``common.word_tile`` picks — the whole width here,
    since n_pad / 32 is not a multiple of 128 for either graph."""
    words = n_pad // 32
    wk = common.word_tile(words)
    assert wk == words
    kern, bs = ((packed_push_sweep, 128) if direction == "push"
                else (packed_pull_sweep, 8))
    _compile(kern, _spec(one_chip, (S, words), jnp.uint32),
             _spec(one_chip, (n_pad, words), jnp.uint32),
             _spec(one_chip, (S, n_pad), jnp.int32),
             _spec(one_chip, (), jnp.int32),
             bs=bs, bn=128, wk=wk, interpret=False)


def test_minplus_dense_compiles(one_chip):
    n = RMAT_SOCIAL_MD
    _compile(fused_minplus_sweep, _spec(one_chip, (S, n), jnp.float32),
             _spec(one_chip, (n, n), jnp.float32),
             _spec(one_chip, (S, n), jnp.float32),
             _spec(one_chip, (), jnp.float32),
             bs=128, bn=128, bk=128, interpret=False)


def test_counting_push_compiles(one_chip):
    n = RMAT_SOCIAL_MD
    _compile(fused_counting_sweep, _spec(one_chip, (S, n), jnp.float32),
             _spec(one_chip, (n, n), jnp.int8),
             _spec(one_chip, (S, n), jnp.int32),
             _spec(one_chip, (S, n), jnp.float32),
             _spec(one_chip, (), jnp.int32),
             bs=128, bn=128, bk=128, interpret=False)


def test_fused_boolean_multisweep_compiles(one_chip):
    n = GRID_ROAD_SM
    _compile(fused_boolean_multisweep, _spec(one_chip, (S, n), jnp.int8),
             _spec(one_chip, (n, n // 32), jnp.uint32),
             _spec(one_chip, (S, n), jnp.int32),
             _spec(one_chip, (), jnp.int32), _spec(one_chip, (), jnp.int32),
             bs=128, max_sweeps=n, interpret=False)


def test_fused_minplus_multisweep_compiles(one_chip):
    n = FUSED_DENSE
    _compile(fused_minplus_multisweep, _spec(one_chip, (S, n), jnp.int8),
             _spec(one_chip, (n, n), jnp.float32),
             _spec(one_chip, (S, n), jnp.float32),
             _spec(one_chip, (), jnp.int32), _spec(one_chip, (), jnp.int32),
             bs=128, max_sweeps=n, interpret=False)


def test_fused_counting_multisweep_compiles(one_chip):
    n = FUSED_DENSE
    _compile(fused_counting_multisweep, _spec(one_chip, (S, n), jnp.int8),
             _spec(one_chip, (n, n), jnp.int8),
             (_spec(one_chip, (S, n), jnp.int32),
              _spec(one_chip, (S, n), jnp.float32)),
             _spec(one_chip, (), jnp.int32), _spec(one_chip, (), jnp.int32),
             bs=128, max_sweeps=n, interpret=False)


KRON15_N = 1 << 15         # bench/configs/kron_s15.json: 2^15 vertices,
KRON15_LANES = 895744      # its m_pad, 128 keys a call
KRON15_KEYS = 128


@pytest.fixture(scope="module")
def sparse_batch(one_chip):
    """``(n, m_pad, keys) -> (device bytes, gather tables, row copies)``
    of the whole ``_run_batch`` program of ``prepare(g, mode="sparse",
    source_batch=keys).apsp(keys)`` over ``n`` vertices and ``m_pad``
    lanes, on destination rows of 8, each shape compiled once.  A gather
    table is the type and layout of the operand a gather reads its slots
    from, ``S(1)`` in the layout where it sits in VMEM; row copies count
    the copies (relayouts) of a ``pred[R, keys]`` array of row ORs."""
    compiled = {}

    def get(n, m_pad, keys):
        if (n, m_pad, keys) not in compiled:
            compiled[n, m_pad, keys] = _compile_sparse_batch(
                one_chip, n, m_pad, keys)
        return compiled[n, m_pad, keys]
    return get


def _compile_sparse_batch(one_chip, n, m_pad, keys):
    n_pad = -(-(n + 1) // 128) * 128
    cfg = EngineConfig(mode="sparse", source_batch=keys)
    r = row_count(m_pad, n)
    w = row_width(m_pad, n)
    assert w == 8
    compiled = _run_batch.lower(
        _spec(one_chip, (1, 1), jnp.int8),
        _spec(one_chip, (1, 1), jnp.uint32),
        _spec(one_chip, (w, r), jnp.int32),
        _spec(one_chip, (r,), jnp.int32),
        _spec(one_chip, (n_pad,), jnp.float32),
        _spec(one_chip, (keys,), jnp.int32),
        _spec(one_chip, (), jnp.int32),
        cfg=cfg, n_real=n, n_pad=n_pad, m_pad=m_pad,
        max_steps=n, use_kernel=True, interpret=False,
        forced_dir=SPARSE, fused_steps=0).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    types = dict(re.findall(r"%(\S+) = (\S+) ", text))
    tables = [types[name]
              for name in re.findall(r" gather\(%([^,\s)]+)", text)]
    row_copies = len(re.findall(rf"= pred\[{r},{keys}\]\S* copy\(", text))
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes), tables, \
        row_copies


def test_graph500_scale20_sparse_batch_fits_one_chip(sparse_batch):
    """The batch program at Graph500 scale 20, on destination rows: the
    gather/scatter state must fit the chip's HBM."""
    used, _, _ = sparse_batch(SCALE20_N, SCALE20_LANES, SCALE20_KEYS)
    assert 0 < used <= V5E_HBM_BYTES, used / 2 ** 30


def test_graph500_scale21_sparse_batch_fits_one_chip(sparse_batch):
    """The same program at scale 21 with the benchmark configuration's
    ``m_pad`` (61 x 2^20 lanes), the largest Graph500 graph one chip
    holds.  The packed gather's slots take 8 bytes, not a byte key padded
    to 128 lanes: 5.97 GiB by this compile (13.6 GiB with the byte
    gather)."""
    used, _, _ = sparse_batch(2 * SCALE20_N, SCALE21_LANES, SCALE20_KEYS)
    assert 0 < used <= 6.5 * 2 ** 30, used / 2 ** 30


@pytest.mark.parametrize("n, m_pad", [(SCALE20_N, SCALE20_LANES),
                                      (2 * SCALE20_N, SCALE21_LANES)])
def test_graph500_sparse_batch_gathers_a_packed_vmem_table(sparse_batch, n,
                                                           m_pad):
    """At scales 20 and 21 the 64 keys' byte table (64 and 128 MiB) is
    past ``PACK_GATHER_BYTES``: the sparse form gathers from the frontier
    packed to two uint32 words a vertex, which XLA places in VMEM.  The
    unpacked row ORs are relaid out for the scatter once, also where it
    runs in two pieces (scale 21)."""
    n_pad = -(-(n + 1) // 128) * 128
    _, tables, row_copies = sparse_batch(n, m_pad, SCALE20_KEYS)
    assert len(tables) == 1, tables
    assert tables[0].startswith(f"u32[2,{n_pad}]{{"), tables
    assert "S(1)" in tables[0], tables
    assert row_copies == 1, row_copies


def test_kron_s15_sparse_batch_still_gathers_bytes(sparse_batch):
    """kron_s15's 128-key byte table (4.2 MB) is under
    ``PACK_GATHER_BYTES`` and already in VMEM: it gathers ``pred`` as
    before packing existed."""
    _, tables, _ = sparse_batch(KRON15_N, KRON15_LANES, KRON15_KEYS)
    assert len(tables) == 1, tables
    assert tables[0].startswith("pred[128,32896]{"), tables
    assert "S(1)" in tables[0], tables
