"""Unified sweep-engine options base.

Every engine in the package (boolean ``apsp_engine``, tropical
``weighted_apsp``, counting ``counting_apsp``/``centrality``, sharded
``sharded_apsp``) takes a frozen, hashable config dataclass as its jit
static argument.  Historically each engine declared its own flat
dataclass; the caller-visible spread (``EngineConfig`` /
``WeightedConfig`` / ``CentralityConfig`` / ``ShardedConfig``) shared
most fields but nothing in the type system said so.

:class:`SweepOptions` is the shared base: the fields every engine
understands (source batching, form selection mode, kernel use, sweep
bound, fused blocks, kernel tiles), and the one place where every
engine resolves its form and its kernel use
(:meth:`SweepOptions.forced_form`, :meth:`SweepOptions.kernel_path`).
The per-engine configs subclass it, adding only their engine-specific
knobs (cost-model constants, extra tile sizes, the sharded semiring
selector), so

  * a plain ``SweepOptions`` can be projected onto any engine config via
    :meth:`SweepOptions.to` (the ``dawn`` facade in ``repro/api.py``
    does exactly this), and
  * ``isinstance(cfg, SweepOptions)`` holds for every engine config —
    the old class names keep working unchanged as thin subclasses.

``max_steps`` is the canonical spelling of the sweep/hop bound;
``WeightedConfig``/``ShardedConfig`` historically called it
``max_sweeps`` and keep that spelling as a synchronized alias (setting
either sets both).
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, ClassVar, Optional, Tuple

import jax

if TYPE_CHECKING:  # import cycle: autotune builds ON options
    from .autotune import TuningPlan

__all__ = ["SweepOptions"]


@dataclasses.dataclass(frozen=True)
class SweepOptions:
    """Engine-agnostic sweep parameters (frozen, hashable — usable as a
    jit static argument).

    ``mode`` names a sweep *form* ("push"/"pull"/"sparse" boolean,
    "dense"/"sparse" tropical) or "auto": the per-sweep occupancy cost
    model picks the form at every sweep, on every backend.  The base
    class accepts any string; each engine subclass pins the set it
    dispatches via ``_mode_names`` and asserts membership.
    """
    source_batch: int = 128          # sources per tile (multiple of 8)
    mode: str = "auto"               # "auto" | an engine form name
    use_kernel: Optional[bool] = None  # None -> Pallas kernels iff on TPU
    max_steps: Optional[int] = None  # None -> n_nodes (hop bound)
    # fused multi-sweep blocks: 0 = off, K > 0 = K sweeps per kernel
    # launch, -1 = whole fixpoint in one launch (kernel path only)
    fused_steps: int = 0
    # kernel tiles (bs adapts to the source batch)
    bn: int = 128
    bk: int = 128
    # optional roofline TuningPlan (core/autotune.py): every engine
    # overlays it via autotune.apply (tiles, fused gate, and the cost
    # model's constants, which is all mode="auto" reads of it).
    # Frozen/hashable, so it rides the jit static arg.
    tuning: Optional["TuningPlan"] = None

    # subclasses pin the form names they dispatch; () = accept anything
    _mode_names: ClassVar[Tuple[str, ...]] = ()

    def __post_init__(self):
        if self._mode_names:
            assert self.mode in ("auto",) + self._mode_names, self.mode
        assert self.source_batch % 8 == 0, \
            f"source_batch must be a multiple of 8, got {self.source_batch}"
        # above one stats/push tile the batch must tile exactly (bs = 128)
        assert self.source_batch <= 128 or self.source_batch % 128 == 0, \
            f"source_batch > 128 must be a multiple of 128, " \
            f"got {self.source_batch}"
        assert self.fused_steps >= -1, \
            f"fused_steps must be -1 (whole fixpoint), 0 (off) or a " \
            f"positive sweep count, got {self.fused_steps}"

    def forced_form(self) -> Optional[int]:
        """The form index ``mode`` pins, in ``_mode_names`` order, or
        None for "auto" (the per-sweep cost model chooses)."""
        return None if self.mode == "auto" else \
            self._mode_names.index(self.mode)

    def kernel_path(self) -> Tuple[bool, bool]:
        """``(use_kernel, interpret)``: Pallas kernels iff on TPU unless
        ``use_kernel`` says otherwise; off the TPU they interpret."""
        on_tpu = jax.default_backend() == "tpu"
        use_kernel = on_tpu if self.use_kernel is None else self.use_kernel
        return use_kernel, not on_tpu

    def to(self, cls, lenient: bool = False, **extra):
        """Project these options onto engine config class ``cls``.

        Copies every shared base field, overlays ``extra``, and lets
        ``cls.__post_init__`` validate.  With ``lenient=True`` a ``mode``
        the target engine does not dispatch falls back to "auto" instead
        of asserting — the facade uses this when one options object
        parameterizes several engines at once (e.g. ``.serve()`` builds
        both the boolean and tropical configs).
        """
        kw = {f.name: getattr(self, f.name)
              for f in dataclasses.fields(SweepOptions)}
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in kw.items() if k in names}
        kw.update(extra)
        valid = getattr(cls, "_mode_names", ())
        if lenient and valid and kw.get("mode", "auto") not in \
                ("auto",) + tuple(valid):
            kw["mode"] = "auto"
        return cls(**kw)
