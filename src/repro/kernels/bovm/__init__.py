from .ops import (sweep, msbfs_kernel, msbfs_packed, pack_adjacency_pull,
                  KernelDawnResult)
from .kernel import (fused_sweep, packed_pull_sweep, packed_push_sweep,
                     fused_boolean_multisweep)
from .ref import sweep_ref, packed_pull_ref, packed_push_ref

from .. import common, registry


def vmem_bytes(*, form: str = "push", bs: int | None = None, bn: int = 128,
               bk: int = 512, wk: int = 128, n: int = 1152, **_) -> int:
    """Resident VMEM of one grid step (docs/ARCHITECTURE.md table).

    ``bs`` defaults to the tile the engine actually dispatches: 128 for
    the push forms, 8 for the bit-packed pull form (``sweep.boolean_forms``
    caps the pull source tile at ``min(s, 8)``).  ``form="fused"`` prices
    the multi-sweep persistent kernel, whose whole packed operand stays
    resident — pass the padded node count ``n``.  Extra keywords are
    ignored so the autotuner can price every KernelSet with one uniform
    call (core/autotune.py).
    """
    if form == "push":   # packed words + i32 dist/acc, i8+i32 out
        return common.pull_vmem_bytes(128 if bs is None else bs, bn, wk,
                                      word_itemsize=4, d_itemsize=4,
                                      acc_itemsize=4, out_itemsizes=(1, 4))
    if form == "push_f32":  # int8 frontier/adj + i32 dist/acc, i8+i32 out
        return common.push_vmem_bytes(128 if bs is None else bs, bn, bk,
                                      f_itemsize=1, a_itemsize=1,
                                      d_itemsize=4, acc_itemsize=4,
                                      out_itemsizes=(1, 4))
    if form == "fused":  # whole (n, W) uint32 operand, its transposed
        b = 128 if bs is None else bs   # copy, and resident tile state
        words = max(n // 32, 1)
        return common.fused_vmem_bytes(
            bs=b, n=n, operand_bytes=2 * n * words * 4,
            frontier_bytes=b * n * (1 + 4),   # i8 rows + (bs, n) u32 words
            state_itemsizes=(4,),          # dist i32 (carried in-register)
            out_itemsizes=(1, 4))          # new i8 + dist i32 out
    assert form == "pull", form    # uint32 words + i32 dist/acc, i8+i32 out
    return common.pull_vmem_bytes(8 if bs is None else bs, bn, wk,
                                  word_itemsize=4, d_itemsize=4,
                                  acc_itemsize=4, out_itemsizes=(1, 4))


registry.register(registry.KernelSet(
    semiring="boolean",
    forms={"push": packed_push_sweep, "push_f32": fused_sweep,
           "pull": packed_pull_sweep},
    vmem_bytes=vmem_bytes,
    notes="bit-packed push AND pull word-AND/OR sweeps (VPU, Eq. 13: no "
          "f32 GEMM on the boolean kernel path; the f32 MXU push survives "
          "as push_f32) + the fused multi-sweep persistent kernel",
    fused_forms={"push": fused_boolean_multisweep},
))
