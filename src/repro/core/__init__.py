"""DAWN core — matrix-operation shortest paths (the paper's contribution)."""
from .frontier import (UNREACHED, pack_bits, unpack_bits, popcount,
                       one_hot_frontier, packed_width)
from .sweep import (Semiring, BOOLEAN, TROPICAL, MIN_LABEL, COUNTING,
                    SEMIRINGS, SweepState, make_state, sweep_loop,
                    boolean_forms, tropical_forms, minlabel_form,
                    counting_forms, minplus_candidates,
                    derive_parents, PUSH, PULL, SPARSE, DIRECTION_NAMES)
from .bovm import bovm_sweep, bovm_msbfs, bovm_sssp, DawnState
from .sovm import sovm_sweep, sovm_sssp, sovm_msbfs, SovmState, reconstruct_path
from .bfs import bfs_queue_numpy, bfs_scipy, bfs_level_sync_jax
from .sssp import sssp, multi_source, apsp, apsp_dense, SsspResult
from .wcc import wcc, wcc_stats, WccResult
from .distributed import (ShardedConfig, ShardedOperands, ShardedApspResult,
                          prepare_sharded, sharded_apsp,
                          SHARDED_FORM_NAMES)
from .weighted import (minplus_sssp, bucketed_sssp, expand_integer_weights,
                       dijkstra_oracle, WeightedResult, weighted_apsp,
                       WeightedApspResult, WeightedConfig,
                       PreparedWeightedGraph, prepare_weighted,
                       WEIGHTED_FORM_NAMES)
from .centrality import (CentralityConfig, CentralityResult, CountingResult,
                         COUNTING_FORM_NAMES, MEASURES, betweenness,
                         brandes_dependencies, centrality, closeness,
                         counting_apsp, counting_apsp_blocks, eccentricity,
                         eccentricity_sample, harmonic)
from .engine import (EngineConfig, SweepStats, ApspResult, PreparedGraph,
                     prepare_graph, frontier_stats, sweep_costs,
                     choose_direction, apsp_engine, apsp_engine_blocks)
from .jobs import (JobMismatchError, JobResult, WORKLOADS, run_sweep_job)
from .autotune import (BackendProfile, GraphStats, TuningPlan,
                       backend_profile, build_plan, device_fingerprint,
                       tune_tiles)

__all__ = [
    "UNREACHED", "pack_bits", "unpack_bits", "popcount", "one_hot_frontier",
    "packed_width",
    "Semiring", "BOOLEAN", "TROPICAL", "MIN_LABEL", "COUNTING", "SEMIRINGS",
    "SweepState", "make_state", "sweep_loop", "boolean_forms",
    "tropical_forms", "minlabel_form", "counting_forms", "derive_parents",
    "bovm_sweep", "bovm_msbfs", "bovm_sssp", "DawnState",
    "sovm_sweep", "sovm_sssp", "sovm_msbfs", "SovmState", "reconstruct_path",
    "bfs_queue_numpy", "bfs_scipy", "bfs_level_sync_jax",
    "sssp", "multi_source", "apsp", "apsp_dense", "SsspResult",
    "wcc", "wcc_stats", "WccResult",
    "ShardedConfig", "ShardedOperands", "ShardedApspResult",
    "prepare_sharded", "sharded_apsp", "SHARDED_FORM_NAMES",
    "minplus_candidates",
    "minplus_sssp", "bucketed_sssp", "expand_integer_weights",
    "dijkstra_oracle", "WeightedResult", "weighted_apsp",
    "WeightedApspResult", "WeightedConfig", "PreparedWeightedGraph",
    "prepare_weighted", "WEIGHTED_FORM_NAMES",
    "CentralityConfig", "CentralityResult", "CountingResult",
    "COUNTING_FORM_NAMES", "MEASURES", "betweenness", "brandes_dependencies",
    "centrality", "counting_apsp", "counting_apsp_blocks", "eccentricity",
    "closeness", "harmonic", "eccentricity_sample",
    "PUSH", "PULL", "SPARSE", "DIRECTION_NAMES", "EngineConfig",
    "SweepStats", "ApspResult", "PreparedGraph", "prepare_graph",
    "frontier_stats", "sweep_costs", "choose_direction",
    "apsp_engine", "apsp_engine_blocks",
    "JobMismatchError", "JobResult", "WORKLOADS", "run_sweep_job",
    "BackendProfile", "GraphStats", "TuningPlan", "backend_profile",
    "build_plan", "device_fingerprint", "tune_tiles",
]

# --- deprecated caller-facing entry points --------------------------------
# The per-semiring functions remain the internal engines (submodule imports
# are unwrapped), but external callers should go through the unified facade
# (repro.prepare).  Each wrapper warns exactly once per process.
import functools as _functools
import warnings as _warnings

from .options import SweepOptions  # noqa: F401  (facade config base)

__all__.append("SweepOptions")


def _deprecated_entry_point(fn, replacement):
    warned = []

    @_functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not warned:
            warned.append(True)
            _warnings.warn(
                f"repro.core.{fn.__name__} is deprecated as a public entry "
                f"point; use {replacement} (the unified dawn facade)",
                DeprecationWarning, stacklevel=2)
        return fn(*args, **kwargs)

    wrapper.__wrapped__ = fn
    return wrapper


apsp_engine = _deprecated_entry_point(
    apsp_engine, "repro.prepare(g).apsp()")
weighted_apsp = _deprecated_entry_point(
    weighted_apsp, "repro.prepare(g, weights=...).apsp(semiring='tropical')")
counting_apsp = _deprecated_entry_point(
    counting_apsp, "repro.prepare(g).apsp(semiring='counting')")
sharded_apsp = _deprecated_entry_point(
    sharded_apsp, "repro.prepare(g).apsp(mesh=...)")
