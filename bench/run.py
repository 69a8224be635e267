"""Run one benchmark cell once, on the chips of this machine.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  Everything about the cell is found by name:
the cell in ``BENCHMARK.json``; its configuration in the file that entry
names; its traffic in ``bench/traffic/<traffic>.json``, whose ``driver``
names ``bench/drivers/<driver>.py``; each per-layer metric in
``bench/metrics/<metric>.py``; the device's peaks in ``bench/peaks.json``.

The run builds the configuration's one graph from the ``graph_seed``
that the configuration fixes, draws its traffic (keys, arrivals, the rows
it checks) from ``--seed``, warms up (that is ``setup_s``), measures for
``--seconds``, checks what the window produced against the plain
reference, and prints one JSON line last on standard output.  With
``--trace 1`` it traces a shorter window and reports the per-layer
metrics in place of the end-to-end ones.  It exits with 2, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, name: str):
    """(workload entry, configuration, traffic) of the named cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return (cell, load_json(ROOT / conf["file"]),
            load_json(BENCH / "traffic" / f"{cell['traffic']}.json"))


def cell_metrics(spec: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in a run of this kind."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in moved)]


def read_metric(name: str, ctx, outcome):
    """Run ``bench/metrics/<name>.py``'s ``read``; None where it finds
    nothing to read."""
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx.reduced, outcome.counters)


def require_chips(n: int):
    """The TPU devices of this machine; raises NoChip without ``n``."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform}, not a TPU")
    if len(devices) < n:
        raise NoChip(f"cell needs {n} chips, JAX found {len(devices)}")
    return devices


def enable_compile_cache() -> None:
    """Persistent compilation cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program is
    cached, however quick its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def start(workload: str):
    """What every process on the chip does first -> (spec, cell,
    configuration, traffic, devices).  Raises NoChip without the chips
    the cell needs; a device without peaks in ``bench/peaks.json`` is an
    error."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cell, config, traffic = find_cell(spec, workload)
    sys.path.insert(0, str(ROOT / "src"))
    devices = require_chips(cell["chips"])
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if devices[0].device_kind not in peaks:
        raise SystemExit(f"no peaks for device {devices[0].device_kind!r} "
                         f"in bench/peaks.json")
    enable_compile_cache()
    return spec, cell, config, traffic, devices


def execute(ctx, driver: str):
    """Run the traffic's driver in ``ctx`` -> Outcome."""
    mod = importlib.import_module(f"bench.drivers.{driver}")
    return mod.run(ctx)


def result_line(spec: dict, cell: dict, ctx, outcome, devices) -> dict:
    """The last line of standard output."""
    metrics = {}
    for m in cell_metrics(spec, cell["name"], ctx.trace):
        if m["name"] == "setup_s":
            value = ctx.setup_s
        elif ctx.trace:
            value = read_metric(m["name"], ctx, outcome)
        else:
            value = outcome.metrics[m["name"]]
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    line = {"correct": all(v <= lim for v, lim in outcome.checks.values()),
            "attempted": outcome.attempted, "failed": outcome.failed,
            "metrics": metrics, "device": device}
    if ctx.trace:
        device["busy_s"] = ctx.reduced.busy_s()
        device["window_s"] = ctx.reduced.window_s
        line["breakdown"] = ctx.reduced.breakdown()
    line["compiles_in_window"] = ctx.compiles_in_window
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in outcome.checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the profile here and keep it")
    args = ap.parse_args(argv)

    try:
        spec, cell, config, traffic, devices = start(args.workload)
    except NoChip as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    line = run_cell(spec, cell, config, traffic, args, devices)
    for k, c in line["checks"].items():
        print(f"check {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, args,
             devices) -> dict:
    """Everything of a run after the look for chips -> the result line."""
    from bench.harness import Context
    from bench.instrument import CompileClock
    ctx = Context(config=config, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  t_start=T_START, keep_trace=args.keep_trace,
                  clock=CompileClock())
    outcome = execute(ctx, traffic["driver"])
    return result_line(spec, cell, ctx, outcome, devices)


if __name__ == "__main__":
    sys.exit(main())
