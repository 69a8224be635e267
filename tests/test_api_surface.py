"""Public-API surface guard + deprecation-shim behavior.

The checked-in snapshot below IS the caller-facing surface of the
package: the unified ``dawn`` facade plus the dynamic-graph types it
fronts.  Growing it is an API decision — update the snapshot in the
same PR and say why — not a side effect of an import added somewhere.
"""
import os
import subprocess
import sys
import warnings

import repro

# the snapshot: repro.__all__, frozen
PUBLIC_SURFACE = [
    "CSRGraph",
    "DawnGraph",
    "DynamicCSRGraph",
    "IncrementalSSSP",
    "IncrementalState",
    "RepairResult",
    "SEMIRING_NAMES",
    "SweepOptions",
    "prepare",
    "repair",
    "sssp_state",
]


def test_public_surface_matches_snapshot():
    assert sorted(repro.__all__) == sorted(PUBLIC_SURFACE)
    for name in repro.__all__:
        assert hasattr(repro, name), f"__all__ exports missing {name}"


def test_importing_repro_does_not_touch_attic():
    code = ("import sys, repro, repro.core, repro.serve, repro.graph; "
            "bad = [m for m in sys.modules if m.startswith('repro._attic')]; "
            "assert not bad, bad; print('clean')")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert "clean" in out.stdout


def test_old_entry_points_warn_exactly_once():
    """Each deprecated per-semiring entry point warns on first call only.

    Runs in a subprocess: the warn-once latch is per-process state, and
    other tests in this session may already have tripped it.
    """
    code = """
import warnings
import numpy as np
from repro.core import apsp_engine, counting_apsp, weighted_apsp
from repro.graph import generators as gen

g = gen.watts_strogatz(32, 4, 0.1, seed=0)
w = np.ones(g.m_pad, np.float32)
for fn, args in ((apsp_engine, (g, [0])),
                 (counting_apsp, (g, [0])),
                 (weighted_apsp, (g, w, [0]))):
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        fn(*args)
        fn(*args)
    dep = [x for x in rec if issubclass(x.category, DeprecationWarning)
           and "deprecated" in str(x.message)]
    assert len(dep) == 1, (fn.__name__, [str(x.message) for x in dep])
print('once-each')
"""
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert "once-each" in out.stdout


def test_attic_serving_engine_shim_warns():
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        from repro.serve import ServingEngine  # noqa: F401
    # warn-once latch: a warning fires only if this is the first touch
    # in the process, so just check nothing *else* leaked and the name
    # resolves to the attic module
    import repro._attic.lm_serving as lm
    from repro import serve
    assert serve.ServingEngine is lm.ServingEngine
    assert all(issubclass(x.category, DeprecationWarning) for x in rec)


def test_deprecated_wrappers_preserve_identity():
    from repro.core import apsp_engine, sharded_apsp
    from repro.core.engine import apsp_engine as raw_engine
    from repro.core.distributed import sharded_apsp as raw_sharded
    assert apsp_engine.__wrapped__ is raw_engine
    assert sharded_apsp.__wrapped__ is raw_sharded
    assert apsp_engine.__name__ == "apsp_engine"
