from .kernel import (fused_minplus_sweep, fused_minplus_multisweep,
                     sparse_relax_sweep)
from .ref import minplus_sweep_ref, sparse_relax_ref

from .. import common, registry


def vmem_bytes(*, form: str = "dense", bs: int = 128, bn: int = 128,
               bk: int = 128, s: int = 64, n_pad: int = 1152,
               eb: int = 128, n: int = 1152, **_) -> int:
    """Resident VMEM of one grid step (docs/ARCHITECTURE.md table).
    Extra keywords are ignored (uniform autotuner call)."""
    if form == "dense":  # f32 fdist + f32 W + f32 dist/acc, i8+f32 out
        return common.push_vmem_bytes(bs, bn, bk, f_itemsize=4, a_itemsize=4,
                                      d_itemsize=4, acc_itemsize=4,
                                      out_itemsizes=(1, 4))
    if form == "fused":  # whole (n, n) f32 weight matrix + resident state
        return common.fused_vmem_bytes(
            bs=bs, n=n, operand_bytes=n * n * 4,
            frontier_bytes=bs * n * (1 + 4),  # i8 rows + f32 fd scratch
            state_itemsizes=(4,),          # dist f32
            out_itemsizes=(1, 4))          # new i8 + dist f32 out
    assert form == "sparse", form
    # i8 frontier + f32 dist/acc/out + i8 out, whole (S, n_pad) state,
    # plus 3 (1, eb) edge-lane blocks (src/dst int32, w f32)
    return s * n_pad * (1 + 4 + 4 + 4 + 1) + 3 * eb * 4


registry.register(registry.KernelSet(
    semiring="tropical",
    forms={"dense": fused_minplus_sweep, "sparse": sparse_relax_sweep},
    vmem_bytes=vmem_bytes,
    notes="fused min-plus push sweep (settled-bound tile skip) + "
          "edge-parallel sparse relax (interpret-validated; prefer the "
          "dense kernel or the XLA sparse form on real TPUs) + the fused "
          "multi-sweep persistent min-plus kernel (whole weight matrix "
          "resident — the VMEM gate in resolve_fused_steps bounds n)",
    # sparse only: data-dependent gathers/scatters by edge index are not
    # validated under Mosaic compilation and the whole-(S, n_pad) state is
    # VMEM-unbounded in n_pad.  The dense form is compiled-dispatchable:
    # its lane loop is common.lane_fold (aligned chunk loads, static lane
    # slices), which tests/test_tpu_compile.py compiles for a v5e.
    interpret_only=frozenset({"sparse"}),
    fused_forms={"dense": fused_minplus_multisweep},
))
