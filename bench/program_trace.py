"""What the program's own ``dawn.*`` names leave in a profiler trace, and
the per-layer readings taken from them.

``bench/trace.py`` reduces a trace to the benchmark's view: device busy
time, device time per operation, and the benchmark's ``bench.*`` spans.
This module reads the same ``.xplane.pb`` once more for what the program
names itself (``src/repro/obs.py``):

  * the ``tf_op`` of every device operation, from the device plane's event
    metadata (``jax.profiler.ProfileData`` shows event stats, not
    metadata stats, so the metadata is decoded here from the protobuf wire
    format); a ``jax.named_scope`` such as ``dawn.sweep.sparse`` is a
    component of it;
  * the ``dawn.*`` host spans, with their arguments and thread;
  * the host's program launches (``PJRT_LoadedExecutable_Execute...``) and
    ``DevicePut`` transfers, with their thread;
  * the idle gaps of ``bench/trace.py``, each labelled by the innermost
    span of either kind open at its midpoint.

Reduce a kept trace (``python3 -m bench.run ... --keep-trace DIR``)::

    python3 -m bench.program_trace --file DIR

or run one traced window of a cell and print the harness's result line
with a ``program`` section added (the readings of :data:`READERS`, the
device time per scope and the labelled idle gaps)::

    python3 -m bench.program_trace --workload kron_s15_p2p --seed 1 \\
        --seconds 40

Times are seconds from the start of the trace.
"""
from __future__ import annotations

from bench import run as bench_run  # first: its clock times the set-up

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

from bench import trace as trace_mod  # noqa: E402

PROGRAM_PREFIX = "dawn."
EXECUTE_PREFIX = "PJRT_LoadedExecutable_Execute"
DEVICE_PUT = "DevicePut"
TF_OP = "tf_op"


# --------------------------------------------------------------------------
# protobuf wire format: just enough of XSpace for the event metadata
# --------------------------------------------------------------------------

def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """(field number, value) of one message: ints for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield num, val


def _map_entry(buf: bytes):
    """(key, value) of a protobuf map entry."""
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map<int64,
# XEventMetadata>), .stat_metadata = 5 (map<int64, XStatMetadata>);
# XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
# XStat.metadata_id = 1, .str_value = 5, .ref_value = 7

def device_tf_ops(raw: bytes) -> dict:
    """Device operation name -> its ``tf_op`` (the HLO ``op_name``), over
    every device plane; a name that two planes give different ``tf_op``s
    maps to None."""
    out = {}
    for num, plane in _fields(raw):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((v for k, v in fields if k == 2), b"").decode()
        if not trace_mod.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                sid, meta = _map_entry(v)
                stat_names[sid] = next(
                    (x for f, x in _fields(meta) if f == 2), b"").decode()
        tf_id = next((i for i, s in stat_names.items() if s == TF_OP), None)
        for k, v in fields:
            if k != 4 or tf_id is None:
                continue
            _, meta = _map_entry(v)
            ev_name, tf_op = None, None
            for f, x in _fields(meta):
                if f == 2:
                    ev_name = x.decode()
                elif f == 5:
                    stat = dict(_fields(x))
                    if stat.get(1) == tf_id:
                        tf_op = stat[5].decode() if 5 in stat \
                            else stat_names.get(stat.get(7))
            if ev_name is None or tf_op is None:
                continue
            tf_op = tf_op.rstrip(":")
            if out.setdefault(ev_name, tf_op) != tf_op:
                out[ev_name] = None
    return out


def in_scope(tf_op: Optional[str], scope: str) -> bool:
    """Whether ``scope`` is a component of the operation's name path."""
    return bool(tf_op) and f"/{scope}/" in f"/{tf_op}/"


def innermost_scope(tf_op: Optional[str]) -> str:
    """The innermost ``dawn.*`` component of ``tf_op``; else its program
    (``jit(_run_batch)``), or ``-`` without a ``tf_op``."""
    if not tf_op:
        return "-"
    parts = tf_op.split("/")
    named = [p for p in parts if p.startswith(PROGRAM_PREFIX)]
    return named[-1] if named else parts[0]


# --------------------------------------------------------------------------
# the reduced trace
# --------------------------------------------------------------------------

@dataclasses.dataclass
class ProgramSpan:
    name: str                      # with its dawn. prefix
    start: float
    end: float
    args: dict
    thread: str

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ProgramTrace:
    base: trace_mod.Trace          # the benchmark's own reduction
    ops: list                      # per device: leaf (tf_op, start, end)
    program_spans: list            # ProgramSpan, by start
    launches: list                 # (kind, start, end, thread)

    def spans(self, name: str) -> list:
        """The ``dawn.<name>`` spans that start inside the window."""
        lo, hi = self.base.window
        full = PROGRAM_PREFIX + name
        return [sp for sp in self.program_spans
                if sp.name == full and lo <= sp.start < hi]

    def scope_s(self, scope: str) -> float:
        """Device seconds in the window of the operations under
        ``dawn.<scope>``, averaged over the devices traced."""
        lo, hi = self.base.window
        full = PROGRAM_PREFIX + scope
        return sum(max(0.0, min(e, hi) - max(s, lo))
                   for ops in self.ops for tf, s, e in ops
                   if in_scope(tf, full)) / len(self.ops)

    def scope_seconds(self) -> dict:
        """Device seconds in the window per innermost scope (or program
        where no scope holds the operation), averaged over devices."""
        lo, hi = self.base.window
        out = {}
        for ops in self.ops:
            for tf, s, e in ops:
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    key = innermost_scope(tf)
                    out[key] = out.get(key, 0.0) + d / len(self.ops)
        return out

    def launches_in(self, start: float, end: float, thread: str) -> dict:
        """Launches of each kind on ``thread`` inside [start, end]."""
        out = {"execute": 0, "device_put": 0}
        for kind, s, e, t in self.launches:
            if t == thread and start <= s and e <= end:
                out[kind] += 1
        return out

    def _label(self, t: float) -> str:
        best = None
        for name, s, e in self.base.spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        for sp in self.program_spans:
            if sp.start <= t <= sp.end and (best is None
                                            or sp.seconds < best[1]):
                best = (sp.name, sp.seconds)
        return best[0] if best else "idle"

    def idle_gaps(self) -> list:
        """``bench/trace.py``'s idle gaps, each labelled by the innermost
        benchmark or program span open at its midpoint, longest first."""
        lo, hi = self.base.window
        busy = trace_mod.merge([iv for b in self.base.busy for iv in b])
        gaps, reach = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > reach and reach < hi:
                gaps.append((reach, min(s, hi)))
            reach = max(reach, e)
        return sorted(((self._label((s + e) / 2), e - s) for s, e in gaps),
                      key=lambda g: -g[1])

    def span_summary(self) -> dict:
        """Per ``dawn.*`` span name in the window: count and mean ms."""
        out = {}
        lo, hi = self.base.window
        for sp in self.program_spans:
            if lo <= sp.start < hi:
                n, tot = out.get(sp.name, (0, 0.0))
                out[sp.name] = (n + 1, tot + sp.seconds)
        return {k: {"count": n, "mean_ms": 1e3 * tot / n}
                for k, (n, tot) in sorted(out.items())}

    def breakdown(self) -> dict:
        scopes = sorted(self.scope_seconds().items(), key=lambda kv: -kv[1])
        return {"device_scopes": [[k, v] for k, v in scopes],
                "idle_gaps": [[k, v] for k, v in
                              self.idle_gaps()[:trace_mod.TOP]],
                "program_spans": self.span_summary()}


def from_events(device_ops, host_spans, program_spans=(),
                launches=()) -> ProgramTrace:
    """``device_ops``: per device, (name, start, end, tf_op); the rest as
    the fields of :class:`ProgramTrace` (``program_spans`` as tuples of
    :class:`ProgramSpan`'s fields)."""
    base = trace_mod.from_events(
        [[(n, s, e) for n, s, e, _ in ops] for ops in device_ops],
        host_spans)
    ops = []
    for dev in device_ops:
        tf = {(n, s, e): t for n, s, e, t in dev}
        ops.append([(tf[o], o[1], o[2])
                    for o in trace_mod.leaves([(n, s, e)
                                               for n, s, e, _ in dev])])
    spans = sorted((ProgramSpan(*sp) for sp in program_spans),
                   key=lambda sp: sp.start)
    return ProgramTrace(base=base, ops=ops, program_spans=spans,
                        launches=list(launches))


def from_profile(profile, tf_ops: dict) -> ProgramTrace:
    """Reduce a ``jax.profiler.ProfileData`` whose device operations'
    ``tf_op``s are ``tf_ops`` (:func:`device_tf_ops`)."""
    device_ops, host_spans, spans, launches = [], [], [], []
    bench_prefix = trace_mod.SPAN_PREFIX
    for plane in profile.planes:
        if trace_mod.DEVICE_PLANE.match(plane.name):
            device_ops.append([
                (ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9,
                 tf_ops.get(ev.name))
                for line in plane.lines if line.name == trace_mod.OPS_LINE
                for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    e = (ev.start_ns + ev.duration_ns) * 1e-9
                    if ev.name.startswith(bench_prefix):
                        host_spans.append((ev.name[len(bench_prefix):], s, e))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        spans.append((ev.name, s, e, dict(ev.stats),
                                      line.name))
                    elif ev.name.startswith(EXECUTE_PREFIX):
                        launches.append(("execute", s, e, line.name))
                    elif ev.name == DEVICE_PUT:
                        launches.append(("device_put", s, e, line.name))
    return from_events(device_ops, host_spans, spans, launches)


def load(path: str) -> ProgramTrace:
    """Reduce the one ``.xplane.pb`` under ``path`` (a directory the
    profiler wrote to, or the file itself; ``.gz`` is read too)."""
    import jax
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"{path}: {len(found)} .xplane.pb files")
        path = found[0]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read()
    profile = jax.profiler.ProfileData.from_serialized_xspace(raw)
    return from_profile(profile, device_tf_ops(raw))


# --------------------------------------------------------------------------
# per-layer readings: (ProgramTrace, the driver's counters) -> value | None
# --------------------------------------------------------------------------

def _mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def apsp_host_ms(pt: ProgramTrace, counters) -> Optional[float]:
    """Mean wall time of ``dawn.apsp`` (ms).  The call returns before the
    device finishes, so this is the facade's and engine shell's host
    work.  Layer: facade and engine shell."""
    m = _mean(sp.seconds for sp in pt.spans("apsp"))
    return None if m is None else 1e3 * m


def host_launches(pt: ProgramTrace, counters) -> Optional[float]:
    """Program launches plus ``DevicePut``s on the span's own thread
    inside each ``dawn.apsp``, mean.  Layer: engine shell."""
    return _mean(sum(pt.launches_in(sp.start, sp.end, sp.thread).values())
                 for sp in pt.spans("apsp"))


def sparse_form_ms(pt: ProgramTrace, counters) -> Optional[float]:
    """Device time of the operations under ``dawn.sweep.sparse`` per
    sparse sweep (``direction_counts[2]``) (ms).  Layer: sweep forms."""
    counts = counters.get("direction_counts")
    busy = pt.scope_s("sweep.sparse")
    if not counts or not counts[2] or not busy:
        return None
    return 1e3 * busy / counts[2]


def choose_share(pt: ProgramTrace, counters) -> Optional[float]:
    """Device time under ``dawn.sweep.choose`` over device-busy time (%).
    Layer: direction choice."""
    busy = pt.scope_s("sweep.choose")
    if not busy:
        return None
    return 100.0 * busy / pt.base.busy_s()


def admit_us(pt: ProgramTrace, counters) -> Optional[float]:
    """Mean wall time of ``dawn.serve.submit`` (us).  Layer: serving
    tiers (admission)."""
    m = _mean(sp.seconds for sp in pt.spans("serve.submit"))
    return None if m is None else 1e6 * m


def flush_host_ms(pt: ProgramTrace, counters) -> Optional[float]:
    """Per ``dawn.serve.flush``: wall time minus the device-busy time
    inside it, mean (ms).  Layer: serving tiers (flush)."""
    m = _mean(sp.seconds - pt.base.busy_s(sp.start, sp.end)
              for sp in pt.spans("serve.flush"))
    return None if m is None else 1e3 * m


def flush_fill(pt: ProgramTrace, counters) -> Optional[float]:
    """Live rows over tile rows, summed over the flushes (%).  Layer:
    serving tiers (buckets)."""
    flushes = pt.spans("serve.flush")
    tile = sum(sp.args.get("tile", 0) for sp in flushes)
    if not tile:
        return None
    return 100.0 * sum(sp.args.get("rows", 0) for sp in flushes) / tile


def queue_wait_ms(pt: ProgramTrace, counters) -> Optional[float]:
    """Mean of the flushes' ``wait_ms``: how long the oldest query of a
    flush waited in its bucket (ms).  Layer: serving tiers (buckets)."""
    return _mean(sp.args["wait_ms"] for sp in pt.spans("serve.flush")
                 if "wait_ms" in sp.args)


READERS = {
    "apsp_host_ms.bfs": apsp_host_ms,
    "host_launches.bfs": host_launches,
    "sparse_form_ms.bfs": sparse_form_ms,
    "choose_share.bfs": choose_share,
    "admit_us.p2p": admit_us,
    "flush_host_ms.p2p": flush_host_ms,
    "flush_fill.p2p": flush_fill,
    "queue_wait_ms.p2p": queue_wait_ms,
}


def readings(pt: ProgramTrace, counters: dict) -> dict:
    """Every reading; None where its span or scope is absent."""
    return {name: fn(pt, counters) for name, fn in READERS.items()}


# --------------------------------------------------------------------------
# command line
# --------------------------------------------------------------------------

def run_traced(args) -> int:
    """One traced run of a cell through the harness -> its result line
    with a ``program`` section."""
    from bench.harness import Context
    from bench.instrument import CompileClock
    try:
        spec, cell, config, traffic, devices = bench_run.start(args.workload)
    except bench_run.NoChip as e:
        print(f"bench.program_trace: {e}", file=sys.stderr)
        return 2
    keep = args.keep_trace or tempfile.mkdtemp(prefix="program_trace_")
    try:
        ctx = Context(config=config, traffic=traffic, seed=args.seed,
                      seconds=args.seconds, trace=True,
                      t_start=bench_run.T_START, keep_trace=keep,
                      clock=CompileClock())
        outcome = bench_run.execute(ctx, traffic["driver"])
        line = bench_run.result_line(spec, cell, ctx, outcome, devices)
        pt = load(keep)
    finally:
        if args.keep_trace is None:
            shutil.rmtree(keep, ignore_errors=True)
    line["program"] = {
        "metrics": readings(pt, outcome.counters),
        "counters": {k: outcome.counters[k] for k in
                     ("direction_counts", "sweeps")
                     if k in outcome.counters},
        "breakdown": pt.breakdown()}
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--file", help="reduce this kept trace and exit")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--keep-trace", default=None,
                    help="write the profile here and keep it")
    args = ap.parse_args(argv)
    if args.file:
        pt = load(args.file)
        print(json.dumps({"metrics": readings(pt, {}),
                          "breakdown": pt.breakdown()}))
        return 0
    if not args.workload:
        ap.error("--workload or --file is required")
    return run_traced(args)


if __name__ == "__main__":
    sys.exit(main())
