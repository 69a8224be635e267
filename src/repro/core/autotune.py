"""HLO/roofline-driven kernel autotuner (ROADMAP item 2b).

Two knobs dominate a sweep's cost and were, until this module, pinned by
hand: the kernel tile shapes validated against the ~16 MB/core VMEM table
in ``kernels/common.py``, and the per-sweep push/pull/sparse switch —
dynamic occupancy cost model on the kernel path, *wall-clock calibration*
(``sweep.time_sweep_forms``) on the reference path.  The calibration is
the one non-deterministic decision in the engine: two identical
``mode="auto"`` runs could race to different pinned directions and
therefore different ``direction_counts``.

:func:`build_plan` replaces both with a static roofline model:

  * a :class:`BackendProfile` supplies peak FLOP/s, HBM bandwidth and the
    per-core VMEM budget (``TPU_PROFILES``, keyed on the TPU's
    ``device_kind`` and seeded from ``launch/mesh.py``'s v5e constants,
    plus a CPU row for the interpreter; any other device is an error);
  * per-(semiring, form) *unit costs* — seconds per modelled work unit —
    come from either the jitted sweep HLO (``launch/hlo_analysis.analyze``
    counts exact FLOPs/bytes, ``launch/roofline.roofline_terms`` converts
    them to a roofline-bound time; deterministic, unlike a timer) or, when
    lowering is unavailable, a static fallback that reproduces the
    engines' historical cost-constant ratios;
  * :func:`tune_tiles` picks the largest MXU-aligned ``bn``/``bk`` that
    every registered KernelSet fits inside the budget, and gates
    ``fused_steps`` on whole-operand residency.

The result is a frozen, hashable, JSON-serializable :class:`TuningPlan`.
Threading: ``SweepOptions.tuning`` carries the plan into every engine
config; each engine calls :func:`apply` (tile/constant overlay, clamped
to the current graph's padding) and consults
:meth:`TuningPlan.pinned_direction` where it used to wall-clock-calibrate
— ``mode="auto"`` becomes a pure function of (plan, graph shape, batch),
so ``direction_counts`` are finally assertable under auto.  Precedence:
an explicit ``mode=`` pin beats the plan, the plan beats calibration.

Import discipline: this module sits *below* the engines (imports
options/sweep/kernels/launch only); ``engine``/``weighted``/
``centrality``/``distributed`` import it, passing their semiring by name.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import common as kernel_common
from ..kernels import registry as kernel_registry
from ..launch.hlo_analysis import analyze_jitted
from ..launch.mesh import HBM_BW, PEAK_FLOPS_BF16
from ..launch.roofline import roofline_terms
from .frontier import UNREACHED
from . import sweep as S
from .options import SweepOptions

__all__ = ["BackendProfile", "GraphStats", "TuningPlan", "FORM_VOCAB",
           "backend_profile", "device_fingerprint", "graph_stats",
           "form_units", "tune_tiles", "build_plan", "apply"]

PLAN_VERSION = 1

# the forms each semiring's engine dispatches, in that engine's direction
# indexing (boolean == sweep.DIRECTION_NAMES, tropical ==
# weighted.WEIGHTED_FORM_NAMES, counting == centrality.COUNTING_FORM_NAMES)
FORM_VOCAB: Dict[str, Tuple[str, ...]] = {
    "boolean": ("push", "pull", "sparse"),
    "tropical": ("dense", "sparse"),
    "counting": ("push", "sparse"),
}

# engine-config cost-constant field per form name
_COST_FIELDS = {"push": "c_push", "pull": "c_pull", "sparse": "c_sparse",
                "dense": "c_dense"}

# static fallback ratio of each form's per-unit cost to the GEMM form's
# (the engines' historical c_* defaults: dense MAC 1, word/lane 8)
_STATIC_RATIO = {"push": 1.0, "dense": 1.0, "pull": 8.0, "sparse": 8.0}


# --------------------------------------------------------------------------
# backend profiles
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendProfile:
    """Roofline constants for one device class.

    ``name`` is the device fingerprint the plan is locked to;
    ``vmem_budget`` is the per-core fast-memory budget tile plans must
    fit (VMEM on TPU; reused as a residency bound elsewhere).
    """
    name: str
    peak_flops: float
    hbm_bw: float
    vmem_budget: int


# Roofline constants per TPU ``device_kind`` (what
# ``jax.devices()[0].device_kind`` reports).  v5e: Google Cloud "TPU v5e"
# documentation, the launch/mesh.py constants — 197 TFLOP/s bf16,
# 819 GB/s HBM.  A TPU kind missing here is an error, not a default.
TPU_PROFILES: Dict[str, BackendProfile] = {
    "TPU v5 lite": BackendProfile("TPU v5 lite", PEAK_FLOPS_BF16, HBM_BW,
                                  kernel_common.VMEM_BUDGET_BYTES),
}
# The CPU backend runs the tests and the Pallas interpreter: its row only
# has to rank forms sanely, and its VMEM budget bounds interpret-mode
# tile choices.  It never prices a device.
CPU_PROFILE = BackendProfile("cpu", 2.0e11, 5.0e10,
                             kernel_common.VMEM_BUDGET_BYTES)


def device_fingerprint() -> str:
    """``backend:device_kind`` of the default device — the identity a
    saved plan refuses to load across (tile/threshold choices do not
    transfer between device classes)."""
    dev = jax.devices()[0]
    return f"{jax.default_backend()}:{getattr(dev, 'device_kind', '?')}"


def backend_profile(fingerprint: Optional[str] = None) -> BackendProfile:
    """Profile for ``fingerprint`` (default: the current device): the
    ``TPU_PROFILES`` row of its device kind on a TPU, the CPU row on the
    CPU backend.  Any other device raises ``ValueError``."""
    fp = fingerprint or device_fingerprint()
    platform, _, kind = fp.partition(":")
    if platform == "tpu" and kind in TPU_PROFILES:
        base = TPU_PROFILES[kind]
    elif platform == "cpu":
        base = CPU_PROFILE
    else:
        raise ValueError(
            f"no roofline profile for device {fp!r}; known TPU kinds: "
            f"{sorted(TPU_PROFILES)} (add a row with its published peaks)")
    return dataclasses.replace(base, name=fp)


# --------------------------------------------------------------------------
# graph statistics (the tuner's view of a graph)
# --------------------------------------------------------------------------

class GraphStats(NamedTuple):
    """Shape/occupancy summary a plan records as provenance."""
    n_nodes: int
    n_edges: int
    n_pad: int
    m_pad: int
    avg_degree: float
    max_degree: int


def graph_stats(g) -> GraphStats:
    """Stats for a ``CSRGraph`` / ``DynamicCSRGraph`` / prepared handle
    (anything with ``.graph`` or the CSR surface itself)."""
    pg_n_pad = getattr(g, "n_pad", None)
    graph = getattr(g, "graph", g)
    if hasattr(graph, "view"):               # DynamicCSRGraph duck-type
        graph = graph.view()
    n_pad = pg_n_pad if pg_n_pad is not None else graph.n_padded(128)
    deg = np.asarray(graph.out_degrees())
    return GraphStats(
        n_nodes=int(graph.n_nodes), n_edges=int(graph.n_edges),
        n_pad=int(n_pad), m_pad=int(graph.m_pad),
        avg_degree=float(graph.n_edges / max(graph.n_nodes, 1)),
        max_degree=int(deg.max()) if deg.size else 0)


def form_units(form: str, *, s: int, n_pad: int, m_pad: int) -> float:
    """Modelled work units of one sweep in ``form`` — the same counts the
    engines' dynamic cost model uses (engine.sweep_costs), evaluated at
    full occupancy: dense GEMM elements for push/dense, uint32 words for
    pull, padded CSR lanes for sparse."""
    if form in ("push", "dense"):
        return float(s) * n_pad * n_pad
    if form == "pull":
        return float(s) * n_pad * max(n_pad // 32, 1)
    if form == "sparse":
        return float(s) * m_pad
    raise ValueError(f"unknown form {form!r}")


# --------------------------------------------------------------------------
# the plan
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TuningPlan:
    """Serializable tuner output: tile sizes, the fused-steps gate, and
    per-(semiring, form) switch costs.  Frozen and hashable — it rides
    inside the engines' jit-static configs.

    ``unit_costs`` is ``((semiring, form, seconds_per_unit), ...)``;
    :meth:`pinned_direction` turns it into the deterministic replacement
    for wall-clock calibration.  ``source`` records whether the costs
    came from HLO analysis ("hlo") or the static fallback ("static").
    """
    backend: str                  # device fingerprint the plan is locked to
    vmem_budget: int              # bytes; budget the tiles were fit against
    peak_flops: float
    hbm_bw: float
    bs: int                       # source tile (informational; engines cap
                                  # at min(batch, 128) as always)
    bn: int                       # output-column tile
    bk: int                       # contraction tile
    fused_steps: int              # -1 = fuse whole fixpoint, 0 = leave off
    unit_costs: Tuple[Tuple[str, str, float], ...]
    graph: GraphStats             # provenance: the graph it was built on
    source: str = "static"        # "hlo" | "static"
    version: int = PLAN_VERSION

    # -- cost queries ------------------------------------------------------

    def unit_cost(self, semiring: str, form: str) -> Optional[float]:
        for sr, f, c in self.unit_costs:
            if sr == semiring and f == form:
                return c
        return None

    def covers(self, semiring: str) -> bool:
        """True when every form the semiring dispatches has a cost."""
        return all(self.unit_cost(semiring, f) is not None
                   for f in FORM_VOCAB.get(semiring, ()))

    def pinned_direction(self, semiring: str, *, s: int, n_pad: int,
                         m_pad: int) -> Optional[int]:
        """argmin form index for a whole batch — the deterministic
        replacement for the calibrated (wall-clock) regime.  Index is in
        the semiring engine's own direction order (FORM_VOCAB).  Returns
        None when the plan lacks a cost for some form."""
        vocab = FORM_VOCAB.get(semiring)
        if not vocab or not self.covers(semiring):
            return None
        costs = [self.unit_cost(semiring, f)
                 * form_units(f, s=s, n_pad=n_pad, m_pad=m_pad)
                 for f in vocab]
        return int(np.argmin(costs))

    # -- budget validation -------------------------------------------------

    def validate(self, n_pad: Optional[int] = None) -> None:
        """Assert every registered KernelSet fits the plan's tiles inside
        ``vmem_budget`` at ``n_pad`` (default: the build graph's).
        Raises ValueError on the first oversized (semiring, form)."""
        n = self.graph.n_pad if n_pad is None else n_pad
        bn = self.bn if n % self.bn == 0 else kernel_common.MXU_ALIGN
        bk = self.bk if n % self.bk == 0 else kernel_common.MXU_ALIGN
        for semiring in sorted(kernel_registry.available()):
            ks = kernel_registry.get(semiring)
            forms = list(ks.forms)
            if self.fused_steps:
                forms += [f"fused:{f}" for f in ks.fused_forms]
            for name in forms:
                form = name.split(":")[-1] if ":" in name else name
                kind = "fused" if name.startswith("fused:") else form
                need = ks.vmem_bytes(form=kind, bs=self.bs, bn=bn, bk=bk,
                                     n=n, n_pad=n)
                if need > self.vmem_budget:
                    raise ValueError(
                        f"TuningPlan tiles (bs={self.bs}, bn={bn}, "
                        f"bk={bk}) blow the VMEM budget for "
                        f"{semiring}/{name} at n_pad={n}: {need} > "
                        f"{self.vmem_budget} bytes")

    # -- serialization -----------------------------------------------------

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["graph"] = list(self.graph)
        d["unit_costs"] = [list(uc) for uc in self.unit_costs]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TuningPlan":
        d = dict(d)
        version = int(d.get("version", 0))
        if version != PLAN_VERSION:
            raise ValueError(
                f"TuningPlan version {version} != {PLAN_VERSION}")
        d["graph"] = GraphStats(*d["graph"])
        d["unit_costs"] = tuple(
            (str(sr), str(f), float(c)) for sr, f, c in d["unit_costs"])
        return cls(**d)

    def checksum(self) -> str:
        """Stable content hash (the bench gate's hard field)."""
        payload = json.dumps(self.to_dict(), sort_keys=True)
        return hashlib.sha256(payload.encode()).hexdigest()[:16]

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)

    @classmethod
    def load(cls, path, *, allow_mismatch: bool = False) -> "TuningPlan":
        """Load a saved plan; refuses a plan built for a different device
        fingerprint unless ``allow_mismatch=True`` (tile and threshold
        choices do not transfer across device classes)."""
        with open(path) as f:
            plan = cls.from_dict(json.load(f))
        here = device_fingerprint()
        if not allow_mismatch and plan.backend != here:
            raise ValueError(
                f"TuningPlan backend fingerprint {plan.backend!r} does "
                f"not match this device ({here!r}); pass "
                f"allow_mismatch=True to override")
        return plan


# --------------------------------------------------------------------------
# tile tuning (the VMEM-budget fit replacing the hard-coded table)
# --------------------------------------------------------------------------

def _tiles_fit(bs: int, bn: int, bk: int, n_pad: int, budget: int) -> bool:
    for semiring in kernel_registry.available():
        ks = kernel_registry.get(semiring)
        for form in ks.forms:
            if ks.vmem_bytes(form=form, bs=bs, bn=bn, bk=bk, n=n_pad,
                             n_pad=n_pad) > budget:
                return False
    return True


def _fused_fits(bs: int, n_pad: int, budget: int) -> bool:
    for semiring in kernel_registry.available():
        ks = kernel_registry.get(semiring)
        if ks.fused_forms and ks.vmem_bytes(
                form="fused", bs=bs, n=n_pad, n_pad=n_pad) > budget:
            return False
    return True


def tune_tiles(profile: BackendProfile, *, n_pad: int
               ) -> Tuple[int, int, int, int]:
    """(bs, bn, bk, fused_steps) for ``n_pad`` under the profile's
    budget: the largest MXU-aligned divisor tiles every registered
    KernelSet fits, and ``fused_steps=-1`` iff every fused form's
    whole-operand residency fits too (else 0 — the per-sweep grids)."""
    bs = kernel_common.MXU_ALIGN
    best = (kernel_common.MXU_ALIGN, kernel_common.MXU_ALIGN)
    cands = kernel_common.tile_candidates(n_pad)
    for bn in cands:
        for bk in cands:
            if bn * bk > best[0] * best[1] and \
                    _tiles_fit(bs, bn, bk, n_pad, profile.vmem_budget):
                best = (bn, bk)
    fused = -1 if _fused_fits(bs, n_pad, profile.vmem_budget) else 0
    return bs, best[0], best[1], fused


# --------------------------------------------------------------------------
# unit-cost extraction
# --------------------------------------------------------------------------

def _static_unit_costs(profile: BackendProfile
                       ) -> Tuple[Tuple[str, str, float], ...]:
    """Fallback costs: the engines' historical cost-constant ratios
    converted to seconds-per-unit on this profile (2 flops per MAC) —
    deterministic and rank-preserving with the old defaults."""
    mac = 2.0 / profile.peak_flops
    return tuple((sr, f, _STATIC_RATIO[f] * mac)
                 for sr in sorted(FORM_VOCAB)
                 for f in FORM_VOCAB[sr])


def _representative_state(s: int, n_pad: int, dtype, unreached, visited_val):
    """The same mid-sweep occupancy measure_sweep_costs uses: ~6%
    frontier, ~25% visited."""
    f = np.zeros((s, n_pad), np.int8)
    f[:, ::17] = 1
    dist = np.full((s, n_pad), unreached, dtype)
    dist[:, ::4] = visited_val
    return jnp.asarray(f), jnp.asarray(dist)


def _form_seconds(form, frontier, state, profile: BackendProfile
                  ) -> Optional[float]:
    """Roofline-bound seconds of one jitted sweep of ``form``, from exact
    HLO flop/byte counts — None when lowering/analysis fails (the caller
    keeps the static cost)."""
    parent = jnp.zeros((1,), jnp.int32)
    try:
        stats = analyze_jitted(
            lambda fr, st, p: form(fr, st, p, jnp.int32(1)),
            frontier, state, parent)
    except Exception:
        return None
    if stats.flops <= 0 and stats.bytes_accessed <= 0:
        return None
    terms = roofline_terms(stats.flops, stats.bytes_accessed,
                           peak_flops=profile.peak_flops,
                           hbm_bw=profile.hbm_bw)
    return max(terms["t_compute_s"], terms["t_memory_s"], 1e-12)


def _hlo_unit_costs(pg, profile: BackendProfile, *, weights, s: int
                    ) -> Dict[Tuple[str, str], float]:
    """Per-(semiring, form) seconds-per-unit from the lowered XLA
    reference sweeps at a representative state.  Tropical forms are
    priced only when ``weights`` are given."""
    g = pg.graph
    n_pad = pg.n_pad
    units = {f: form_units(f, s=s, n_pad=n_pad, m_pad=g.m_pad)
             for forms in FORM_VOCAB.values() for f in forms}
    out: Dict[Tuple[str, str], float] = {}

    f0, dist = _representative_state(s, n_pad, np.int32, int(UNREACHED), 1)
    bool_forms = S.boolean_forms(pg.adj, pg.adj_pull, pg.rows, n_pad=n_pad,
                                 s=s)
    for name, form in zip(FORM_VOCAB["boolean"], bool_forms):
        t = _form_seconds(form, f0, dist, profile)
        if t is not None:
            out[("boolean", name)] = t / units[name]

    sigma = (np.asarray(dist) >= 0).astype(np.float32)
    cnt_forms = S.counting_forms(pg.adj, g.src, g.dst, n_pad=n_pad, s=s)
    for name, form in zip(FORM_VOCAB["counting"], cnt_forms):
        t = _form_seconds(form, f0, (dist, jnp.asarray(sigma)), profile)
        if t is not None:
            out[("counting", name)] = t / units[name]

    if weights is not None:
        w = np.asarray(weights, np.float32)
        lanes = np.full(g.m_pad, np.inf, np.float32)
        lanes[: g.n_edges] = w[: g.n_edges]
        wdense = jnp.full((n_pad, n_pad), jnp.inf,
                          jnp.float32).at[g.src, g.dst].min(
                              jnp.asarray(lanes))
        fw, dw = _representative_state(s, n_pad, np.float32, np.inf, 1.0)
        trop_forms = S.tropical_forms(wdense, g.src, g.dst,
                                      jnp.asarray(lanes), n_pad=n_pad)
        for name, form in zip(FORM_VOCAB["tropical"], trop_forms):
            t = _form_seconds(form, fw, dw, profile)
            if t is not None:
                out[("tropical", name)] = t / units[name]
    return out


# --------------------------------------------------------------------------
# plan construction + config overlay
# --------------------------------------------------------------------------

def build_plan(g, *, weights=None, profile: Optional[BackendProfile] = None,
               source_batch: int = 8, use_hlo: bool = True) -> TuningPlan:
    """Build a :class:`TuningPlan` for graph ``g`` (CSRGraph /
    DynamicCSRGraph / PreparedGraph).

    ``use_hlo=True`` prices each reference sweep form from its compiled
    HLO (exact flop/byte counts → roofline time; deterministic), falling
    back per-form to the static table when lowering fails; ``False``
    skips lowering entirely — cheapest, fully static, still deterministic
    (the differential suite and the bench gate use this).  ``weights``
    enables tropical-form pricing on the HLO path.
    """
    prof = profile or backend_profile()
    stats = graph_stats(g)
    bs, bn, bk, fused = tune_tiles(prof, n_pad=stats.n_pad)
    costs = {(sr, f): c for sr, f, c in _static_unit_costs(prof)}
    source = "static"
    if use_hlo:
        from .engine import PreparedGraph, prepare_graph
        pg = g if isinstance(g, PreparedGraph) else prepare_graph(g)
        measured = _hlo_unit_costs(pg, prof, weights=weights,
                                   s=source_batch)
        if measured:
            costs.update(measured)
            source = "hlo"
    plan = TuningPlan(
        backend=prof.name, vmem_budget=prof.vmem_budget,
        peak_flops=prof.peak_flops, hbm_bw=prof.hbm_bw,
        bs=bs, bn=bn, bk=bk, fused_steps=fused,
        unit_costs=tuple((sr, f, costs[(sr, f)])
                         for sr in sorted(FORM_VOCAB)
                         for f in FORM_VOCAB[sr]),
        graph=stats, source=source)
    plan.validate()
    return plan


def _cost_overrides(plan: TuningPlan, semiring: str, fields) -> dict:
    """Normalized cost-constant overlays for an engine config: each
    form's per-unit cost relative to the GEMM form's (so the overlay has
    the same scale as the hand-set defaults).  The sharded executor names
    its GEMM form ``c_dense`` for every semiring — map push onto it when
    the target has no ``c_push``."""
    vocab = FORM_VOCAB[semiring]
    base = plan.unit_cost(semiring, vocab[0])
    if not base:
        return {}
    out = {}
    for form in vocab:
        c = plan.unit_cost(semiring, form)
        if c is None:
            continue
        fld = _COST_FIELDS[form]
        if fld not in fields and form == "push" and "c_dense" in fields:
            fld = "c_dense"
        if fld in fields:
            out[fld] = float(c / base)
    return out


def apply(cfg: SweepOptions, *, semiring: str,
          n_pad: Optional[int] = None) -> SweepOptions:
    """Overlay ``cfg.tuning`` onto an engine config: tile sizes (clamped
    back to MXU_ALIGN when they don't divide this graph's ``n_pad``),
    the fused-steps gate (only when the caller left ``fused_steps`` at
    its 0 default — an explicit request wins), and the dynamic cost
    model's constants.  A config with no plan passes through unchanged.
    """
    plan = cfg.tuning
    if plan is None or semiring not in FORM_VOCAB:
        return cfg
    fields = {f.name for f in dataclasses.fields(type(cfg))}
    kw = {}
    bn, bk = plan.bn, plan.bk
    if n_pad is not None:
        if n_pad % bn:
            bn = kernel_common.MXU_ALIGN
        if n_pad % bk:
            bk = kernel_common.MXU_ALIGN
    if "bn" in fields:
        kw["bn"] = bn
    if "bk" in fields:
        kw["bk"] = bk
    if "fused_steps" in fields and cfg.fused_steps == 0 and plan.fused_steps:
        kw["fused_steps"] = plan.fused_steps
    kw.update(_cost_overrides(plan, semiring, fields))
    return dataclasses.replace(cfg, **kw) if kw else cfg
