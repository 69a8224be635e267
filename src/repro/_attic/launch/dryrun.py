import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) on the
production meshes, dump memory/cost analyses + the collective schedule.

The two lines above MUST run before any other import (jax locks the device
count at first init).

Usage:
    python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse      # noqa: E402
import gzip          # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402


from ..configs import all_cells, shapes_for          # noqa: E402
from .cells import build_cell, jit_cell              # noqa: E402
from repro.launch.mesh import make_production_mesh               # noqa: E402

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUP_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUP_RE2 = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every shape literal in an HLO result type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_CONVERT_RE = re.compile(
    r"= f32\[([\d,]+)\]\{[^}]*\} convert\(")


def bf16_promotion_bytes(hlo: str, min_bytes: int = 64 << 20) -> int:
    """XLA:CPU has no bf16 matmul — it f32-converts bf16 dot operands and
    hoists whole stacked-weight conversions out of loops.  A real TPU (bf16
    MXU) never allocates these.  Sum the big f32 convert results so the
    memory report can show a TPU-corrected temp estimate."""
    total = 0
    for m in _CONVERT_RE.finditer(hlo):
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n * 4 >= min_bytes:
            total += n * 4
    return total


def parse_collectives(hlo: str):
    """Per-op collective inventory from post-SPMD HLO text.

    Returns list of {op, bytes (result, per device), group_size,
    in_entry (bool)} — wire-byte conversion happens in the roofline pass."""
    out = []
    cur_comp = ""
    entry = None
    for line in hlo.splitlines():
        m = re.match(r"^(%?[\w\.\-]+)\s*(\([^)]*\))?\s*->", line.strip())
        if line.startswith("ENTRY"):
            cur_comp = "ENTRY"
            continue
        if m and "=" not in line.split("->")[0]:
            cur_comp = m.group(1)
            continue
        stripped = line.strip()
        for col in _COLLECTIVES:
            # match op kind at the instruction position: "= TYPE op-name("
            if f" {col}(" in stripped or f" {col}-start(" in stripped:
                rhs = stripped.split("=", 1)
                if len(rhs) != 2:
                    continue
                result_type = rhs[1].strip().split(col)[0]
                nbytes = _shape_bytes(result_type)
                g = _GROUP_RE.search(stripped)
                if g:
                    group = len(g.group(1).split(","))
                else:
                    g2 = _GROUP_RE2.search(stripped)
                    group = int(g2.group(2)) if g2 else 1
                out.append({"op": col, "bytes": nbytes,
                            "group_size": group,
                            "comp": cur_comp,
                            "in_entry": cur_comp == "ENTRY"})
                break
    return out


def run_cell(arch: str, shape: str, *, multi_pod: bool, out_dir: str):
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    with jax.sharding.set_mesh(mesh):
        cell = build_cell(arch, shape, mesh)
        jitted = jit_cell(cell, mesh)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    promo = bf16_promotion_bytes(hlo)

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "n_devices": mesh.devices.size,
        "kind": cell.kind, "meta": cell.meta,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            # live peak: args + outputs + temps, minus donated aliases
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "output_size_in_bytes", 0) or 0)
            - (getattr(mem, "alias_size_in_bytes", 0) or 0),
            "bf16_promotion_bytes": promo,
        },
        "cost": {k: cost.get(k) for k in
                 ("flops", "bytes accessed", "transcendentals")} if cost
        else {},
        "collectives": {
            "n_ops": len(colls),
            "ops": colls[:512],
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{mesh_name}__{arch}__{shape}"
    path = os.path.join(out_dir, stem + ".json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    with gzip.open(os.path.join(out_dir, stem + ".hlo.gz"), "wt") as f:
        f.write(hlo)

    print(f"[dryrun] {arch} × {shape} on {mesh_name}: "
          f"compile {t_compile:.1f}s")
    print(f"  memory_analysis: {mem}")
    if cost:
        print(f"  cost_analysis: flops={cost.get('flops'):.3e} "
              f"bytes={cost.get('bytes accessed'):.3e}")
    print(f"  collectives: {len(colls)} sites, "
          f"{sum(c['bytes'] for c in colls):.3e} result bytes")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        cells = all_cells()
    else:
        assert args.arch, "--arch required without --all"
        shapes = [args.shape] if args.shape else list(shapes_for(args.arch))
        cells = [(args.arch, s) for s in shapes]

    failures = []
    for arch, shape in cells:
        try:
            run_cell(arch, shape, multi_pod=args.multi_pod, out_dir=args.out)
        except Exception as e:  # noqa: BLE001
            failures.append((arch, shape, repr(e)))
            traceback.print_exc()
    if failures:
        print(f"FAILED {len(failures)}/{len(cells)}:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print(f"dry-run OK: {len(cells)} cells")


if __name__ == "__main__":
    main()
