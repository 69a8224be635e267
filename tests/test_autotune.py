"""The roofline autotuner (core/autotune.py): plan construction, the
determinism lock, serialization, VMEM-budget validation, and the
differential guarantee that tuning may change speed but never results.

Three claims under test (ISSUE 10 acceptance criteria):

  1. Autotuned configs are bit-identical (dist/parent/sigma) to default
     configs on every adversarial family × boolean/tropical/counting ×
     ref/kernel path.
  2. A pinned TuningPlan makes two ``mode="auto"`` runs agree on
     ``direction_counts`` — the plan's analytic argmin replaces the
     wall-clock calibration race (the PR 9 non-determinism).
  3. ``save`` → ``load`` round-trips exactly, refuses a foreign backend
     fingerprint, and every emitted tile shape fits the
     push/pull/fused VMEM budgets of every registered KernelSet.
"""
import dataclasses
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.graph.csr import CSRGraph
from repro.core import autotune
from repro.core.autotune import (FORM_VOCAB, TuningPlan, backend_profile,
                                 build_plan, form_units, graph_stats,
                                 tune_tiles)
from repro.core.engine import EngineConfig, apsp_engine, prepare_graph
from repro.core.weighted import WeightedConfig, weighted_apsp
from repro.core.centrality import CentralityConfig, counting_apsp
from repro.kernels import common as kernel_common
from repro.kernels import registry as kernel_registry

from oracles import adversarial_families

_FAMILIES = {name: (src, dst, n)
             for name, src, dst, n in adversarial_families(seed=0)}


def _graph(family):
    src, dst, n = _FAMILIES[family]
    return CSRGraph.from_edges(src, dst, n)


def _sources(n):
    return np.unique(np.clip([0, 1, n // 2, n - 1], 0, n - 1)).astype(
        np.int32)


def _family_weights(g):
    gs, gd = g.edge_arrays_np()
    return ((gs * 7 + gd * 3) % 9 + 1).astype(np.float32)


@pytest.fixture(scope="module")
def plan_cache():
    """One static plan per family (build_plan is deterministic, so
    sharing across tests in the module is sound)."""
    cache = {}

    def get(family):
        if family not in cache:
            cache[family] = build_plan(_graph(family), use_hlo=False)
        return cache[family]

    return get


# --------------------------------------------------------------------------
# differential suite: tuning may change speed, never results
# --------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ref", "kernel"])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_autotuned_bit_identical_boolean(family, use_kernel, plan_cache):
    g = _graph(family)
    sources = _sources(g.n_nodes)
    base_cfg = EngineConfig(source_batch=8, use_kernel=use_kernel)
    tuned_cfg = dataclasses.replace(base_cfg, tuning=plan_cache(family))
    base = apsp_engine(g, sources, config=base_cfg)
    tuned = apsp_engine(g, sources, config=tuned_cfg)
    np.testing.assert_array_equal(np.asarray(base.dist),
                                  np.asarray(tuned.dist), err_msg=family)
    assert int(base.sweeps) == int(tuned.sweeps), family
    from repro.core import sweep as S
    np.testing.assert_array_equal(
        np.asarray(S.derive_parents(g, base.dist)),
        np.asarray(S.derive_parents(g, tuned.dist)), err_msg=family)


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ref", "kernel"])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_autotuned_bit_identical_tropical(family, use_kernel, plan_cache):
    g = _graph(family)
    w = _family_weights(g)
    sources = _sources(g.n_nodes)
    base_cfg = WeightedConfig(source_batch=8, use_kernel=use_kernel)
    tuned_cfg = dataclasses.replace(base_cfg, tuning=plan_cache(family))
    base = weighted_apsp(g, w, sources, config=base_cfg)
    tuned = weighted_apsp(g, w, sources, config=tuned_cfg)
    np.testing.assert_array_equal(np.asarray(base.dist),
                                  np.asarray(tuned.dist), err_msg=family)
    assert int(base.sweeps) == int(tuned.sweeps), family


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["ref", "kernel"])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_autotuned_bit_identical_counting(family, use_kernel, plan_cache):
    g = _graph(family)
    sources = _sources(g.n_nodes)
    base_cfg = CentralityConfig(source_batch=8, use_kernel=use_kernel)
    tuned_cfg = dataclasses.replace(base_cfg, tuning=plan_cache(family))
    base = counting_apsp(g, sources, config=base_cfg)
    tuned = counting_apsp(g, sources, config=tuned_cfg)
    np.testing.assert_array_equal(np.asarray(base.dist),
                                  np.asarray(tuned.dist), err_msg=family)
    np.testing.assert_array_equal(np.asarray(base.sigma),
                                  np.asarray(tuned.sigma), err_msg=family)


# --------------------------------------------------------------------------
# the determinism lock (the PR 9 mode="auto" regression)
# --------------------------------------------------------------------------

def test_auto_direction_counts_deterministic_with_plan():
    """Two identical mode="auto" runs with the same pinned plan must
    report identical direction_counts — and the pinned direction is
    exactly the plan's analytic argmin, not a timing race."""
    g = _graph("random_ragged")
    plan = build_plan(g, use_hlo=False)
    cfg = EngineConfig(source_batch=16, mode="auto", use_kernel=False,
                       tuning=plan)
    r1 = apsp_engine(g, config=cfg)
    r2 = apsp_engine(g, config=cfg)
    np.testing.assert_array_equal(np.asarray(r1.direction_counts),
                                  np.asarray(r2.direction_counts))
    pg = prepare_graph(g)
    want = plan.pinned_direction("boolean", s=16, n_pad=pg.n_pad,
                                 m_pad=g.m_pad)
    counts = np.asarray(r1.direction_counts)
    assert counts.sum() > 0
    # every sweep ran in the plan-pinned form
    assert counts[want] == counts.sum(), (counts, want)


def test_auto_deterministic_through_jobs_layer():
    """The same lock holds through the resumable-job layer (chunked
    runs resolve the direction per chunk from the same plan)."""
    from repro.core.jobs import run_sweep_job
    from repro.core.options import SweepOptions
    g = _graph("two_components")
    plan = build_plan(g, use_hlo=False)
    opts = SweepOptions(source_batch=8, mode="auto", use_kernel=False,
                        tuning=plan)
    j1 = run_sweep_job(g, list(range(16)), workload="boolean",
                       options=opts)
    j2 = run_sweep_job(g, list(range(16)), workload="boolean",
                       options=opts)
    np.testing.assert_array_equal(np.asarray(j1.dist), np.asarray(j2.dist))
    np.testing.assert_array_equal(np.asarray(j1.direction_counts),
                                  np.asarray(j2.direction_counts))


@pytest.mark.parametrize("semiring", sorted(FORM_VOCAB))
def test_pinned_direction_is_analytic_argmin(semiring):
    plan = build_plan(_graph("path"), use_hlo=False)
    stats = graph_stats(_graph("path"))
    idx = plan.pinned_direction(semiring, s=8, n_pad=stats.n_pad,
                                m_pad=stats.m_pad)
    vocab = FORM_VOCAB[semiring]
    costs = [plan.unit_cost(semiring, f)
             * form_units(f, s=8, n_pad=stats.n_pad, m_pad=stats.m_pad)
             for f in vocab]
    assert idx == int(np.argmin(costs))
    assert 0 <= idx < len(vocab)


def test_hlo_plan_build_is_deterministic():
    """The HLO-extraction path (exact flop/byte counts off the compiled
    sweep HLO) yields the same plan twice in a process — the property
    wall-clock calibration lacked."""
    g = _graph("two_components")
    w = _family_weights(g)
    p1 = build_plan(g, weights=w, use_hlo=True)
    p2 = build_plan(g, weights=w, use_hlo=True)
    assert p1 == p2
    assert p1.checksum() == p2.checksum()
    assert p1.source == "hlo"
    assert all(c > 0 and np.isfinite(c) for _, _, c in p1.unit_costs)
    # every semiring's full form vocabulary is priced
    for semiring in FORM_VOCAB:
        assert p1.covers(semiring), semiring


# --------------------------------------------------------------------------
# serialization properties
# --------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_plan_save_load_roundtrip(tmp_path, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 200))
    m = int(rng.integers(1, 4 * n))
    g = CSRGraph.from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n)
    plan = build_plan(g, use_hlo=False)
    path = tmp_path / "plan.json"
    plan.save(path)
    loaded = TuningPlan.load(path)
    assert loaded == plan
    assert loaded.checksum() == plan.checksum()
    # the on-disk form is plain sorted JSON (inspectable, diffable)
    with open(path) as f:
        raw = json.load(f)
    assert raw["version"] == autotune.PLAN_VERSION
    assert TuningPlan.from_dict(raw) == plan


def test_plan_load_refuses_foreign_fingerprint(tmp_path):
    plan = build_plan(_graph("tiny"), use_hlo=False)
    alien = dataclasses.replace(plan, backend="tpu:v9000-imaginary")
    path = tmp_path / "alien.json"
    alien.save(path)
    with pytest.raises(ValueError, match="fingerprint"):
        TuningPlan.load(path)
    assert TuningPlan.load(path, allow_mismatch=True) == alien


def test_plan_load_refuses_wrong_version(tmp_path):
    plan = build_plan(_graph("tiny"), use_hlo=False)
    d = plan.to_dict()
    d["version"] = 999
    path = tmp_path / "future.json"
    with open(path, "w") as f:
        json.dump(d, f)
    with pytest.raises(ValueError, match="version"):
        TuningPlan.load(path)


# --------------------------------------------------------------------------
# VMEM-budget validation of emitted tiles
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n_pad", [128, 256, 512, 1024, 4096])
def test_emitted_tiles_fit_every_vmem_budget(n_pad):
    """Every tile shape the tuner emits fits the per-grid-step budgets
    of every registered KernelSet (push/pull/fused estimators) at the
    n_pad it was tuned for — checked through both plan.validate and the
    raw kernels/common.py budget math."""
    prof = backend_profile()
    bs, bn, bk, fused = tune_tiles(prof, n_pad=n_pad)
    assert n_pad % bn == 0 and n_pad % bk == 0
    assert bn in kernel_common.TILE_CANDIDATES
    assert bk in kernel_common.TILE_CANDIDATES
    for semiring in sorted(kernel_registry.available()):
        ks = kernel_registry.get(semiring)
        for form in ks.forms:
            assert ks.vmem_bytes(form=form, bs=bs, bn=bn, bk=bk,
                                 n=n_pad, n_pad=n_pad) \
                <= prof.vmem_budget, (semiring, form)
        if fused:
            for form in ks.fused_forms:
                assert ks.vmem_bytes(form="fused", bs=bs, n=n_pad,
                                     n_pad=n_pad) <= prof.vmem_budget, \
                    (semiring, form)
    # the same invariant through the raw budget math the estimators wrap
    assert kernel_common.push_vmem_bytes(
        bs, bn, bk, f_itemsize=1, a_itemsize=1, d_itemsize=4,
        acc_itemsize=4, out_itemsizes=(1, 4)) <= prof.vmem_budget
    assert kernel_common.pull_vmem_bytes(
        8, bn, max(n_pad // 32, 1), word_itemsize=4, d_itemsize=4,
        acc_itemsize=4, out_itemsizes=(1, 4)) <= prof.vmem_budget


def test_plan_validate_rejects_oversized_tiles():
    plan = build_plan(_graph("random_ragged"), use_hlo=False)
    plan.validate()                      # the emitted plan passes
    bloated = dataclasses.replace(plan, vmem_budget=1024)
    with pytest.raises(ValueError, match="VMEM budget"):
        bloated.validate()


def test_apply_clamps_foreign_tiles_to_divisors():
    """A plan built for a large padding overlays onto a smaller graph
    with its tiles clamped back to MXU_ALIGN when they don't divide —
    shared options objects stay usable across graphs."""
    big = CSRGraph.from_edges([0], [1], 500)         # n_pad = 512
    plan = build_plan(big, use_hlo=False)
    assert (plan.bn, plan.bk) == (512, 512)
    cfg = EngineConfig(tuning=plan)
    small = autotune.apply(cfg, semiring="boolean", n_pad=256)
    assert (small.bn, small.bk) == (128, 128)
    same = autotune.apply(cfg, semiring="boolean", n_pad=512)
    assert (same.bn, same.bk) == (512, 512)
    # an explicit fused_steps request survives the overlay
    explicit = autotune.apply(
        EngineConfig(tuning=plan, fused_steps=3), semiring="boolean",
        n_pad=512)
    assert explicit.fused_steps == 3
    assert same.fused_steps == plan.fused_steps


def test_apply_without_plan_is_identity():
    cfg = EngineConfig(source_batch=32)
    assert autotune.apply(cfg, semiring="boolean", n_pad=256) is cfg


def test_plan_is_hashable_static_arg():
    """Plans ride inside jit-static engine configs — they must hash."""
    plan = build_plan(_graph("tiny"), use_hlo=False)
    cfg = EngineConfig(tuning=plan)
    assert hash(cfg) == hash(dataclasses.replace(cfg))
    assert cfg == dataclasses.replace(cfg)


# --------------------------------------------------------------------------
# facade integration
# --------------------------------------------------------------------------

def test_facade_tune_and_reload(tmp_path):
    import repro as dawn
    g = _graph("two_components")
    h = dawn.prepare(g, source_batch=8, mode="auto", use_kernel=False)
    path = tmp_path / "plan.json"
    plan = h.tune(use_hlo=False, save=path)
    assert h.tuning is plan
    r1 = h.apsp()
    h2 = dawn.prepare(g, source_batch=8, mode="auto", use_kernel=False,
                      tuning=str(path))
    assert h2.tuning == plan
    r2 = h2.apsp()
    np.testing.assert_array_equal(np.asarray(r1.dist), np.asarray(r2.dist))
    np.testing.assert_array_equal(np.asarray(r1.direction_counts),
                                  np.asarray(r2.direction_counts))


# --------------------------------------------------------------------------
# backend profiles: keyed on the TPU's device_kind, no silent default
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fingerprint,peak", [
    ("tpu:TPU v5 lite", 197e12),
    ("cpu:cpu", 2.0e11),
])
def test_backend_profile_known_devices(fingerprint, peak):
    prof = backend_profile(fingerprint)
    assert prof.name == fingerprint and prof.peak_flops == peak


@pytest.mark.parametrize("fingerprint", ["tpu:TPU v9 imaginary",
                                         "gpu:NVIDIA H100"])
def test_backend_profile_unknown_device_is_an_error(fingerprint):
    with pytest.raises(ValueError, match="no roofline profile"):
        backend_profile(fingerprint)
