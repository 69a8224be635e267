"""Shared test fixtures.

The suite runs on the CPU, with the Pallas kernels in interpret mode,
whatever accelerator the machine holds: ``JAX_PLATFORMS`` defaults to
``cpu`` here, before anything imports JAX, and the tests that start
child processes pass it to them.  The chip is driven by
``chip_smoke.py``; ``tests/test_tpu_compile.py`` compiles for a described
v5e without one.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.graph.csr import CSRGraph  # noqa: E402


@pytest.fixture
def random_weighted():
    """Factory fixture: seeded random directed graph + non-negative f32
    edge weights over the padded lanes — the graphs both the
    sweep-equivalence and the kernel-equivalence suites run on."""
    def make(n, avg_deg, seed):
        rng = np.random.default_rng(seed)
        m = max(1, int(n * avg_deg))
        g = CSRGraph.from_edges(rng.integers(0, n, m),
                                rng.integers(0, n, m), n)
        w = rng.uniform(0.1, 5.0, g.m_pad).astype(np.float32)
        return g, w
    return make
