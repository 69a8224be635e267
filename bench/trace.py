"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes one ``.xplane.pb`` per traced window.  From it:

  * device busy time — the union of the intervals in which an XLA
    operation ran on a device plane (``/device:TPU:<i>``, line
    ``XLA Ops``), clipped to the window;
  * the window — the benchmark's own ``bench.window`` span;
  * host spans — every ``bench.<name>`` annotation on a host plane;
  * the breakdown — device time per operation, leaf operations only (a
    ``while`` holds its body's operations on the same line), named by
    XLA's instruction name and output shape; and the longest idle gaps,
    each named after the innermost benchmark span open at its midpoint
    (``idle`` where none is).

Times are seconds from the start of the trace.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re

import jax

from bench.instrument import SPAN_PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
TOP = 10


def op_name(text: str) -> str:
    """``%fusion.26 = s8[31457280,64]{...} fusion(...)`` ->
    ``fusion.26 s8[31457280,64]``."""
    head, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}".strip()[:120]


def leaves(ops):
    """The operations of one line that enclose no other."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for i, o in enumerate(ops)
            if i + 1 == len(ops) or ops[i + 1][1] >= o[2]]


def merge(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by disjoint sorted intervals."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclasses.dataclass
class Trace:
    window: tuple                  # (start, end) of bench.window
    busy: list                     # per device: merged busy intervals
    spans: list                    # (name, start, end), window excluded
    op_seconds: dict               # op name -> device seconds in window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self, lo=None, hi=None) -> float:
        """Device-busy seconds in [lo, hi] (default: the window),
        averaged over the devices traced."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        return sum(covered(b, lo, hi) for b in self.busy) / len(self.busy)

    def named(self, name: str):
        return [(s, e) for n, s, e in self.spans if n == name]

    def idle_gaps(self):
        """(label, seconds) of every gap in which no device was busy,
        longest first."""
        lo, hi = self.window
        busy = merge([iv for b in self.busy for iv in b])
        gaps, reach = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > reach and reach < hi:
                gaps.append((reach, min(s, hi)))
            reach = max(reach, e)
        return sorted(((self._label((s + e) / 2), e - s) for s, e in gaps),
                      key=lambda g: -g[1])

    def _label(self, t: float) -> str:
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0] if best else "idle"

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:TOP]],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:TOP]]}


def from_events(device_ops, host_spans) -> Trace:
    """``device_ops``: per device, a list of (name, start, end);
    ``host_spans``: (name, start, end) with the ``bench.`` prefix cut."""
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} bench.window spans")
    lo, hi = windows[0]
    if not device_ops:
        raise ValueError("trace holds no device plane")
    busy, op_seconds = [], {}
    for ops in device_ops:
        busy.append(merge((max(s, lo), min(e, hi)) for _, s, e in ops
                          if e > lo and s < hi))
        for name, s, e in leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = op_name(name)
                op_seconds[key] = op_seconds.get(key, 0.0) + d
    spans = [sp for sp in host_spans if sp[0] != WINDOW]
    return Trace(window=(lo, hi), busy=busy, spans=spans,
                 op_seconds=op_seconds)


def from_profile(profile) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    device_ops, host_spans = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(ev.name, ev.start_ns * 1e-9,
                    (ev.start_ns + ev.duration_ns) * 1e-9)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host_spans.append(
                            (ev.name[len(SPAN_PREFIX):], ev.start_ns * 1e-9,
                             (ev.start_ns + ev.duration_ns) * 1e-9))
    return from_events(device_ops, host_spans)


def load(path: str) -> Trace:
    """Reduce the one ``.xplane.pb`` under ``path`` (a directory the
    profiler wrote to, or the file itself; ``.gz`` is read too)."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"{path}: {len(found)} .xplane.pb files")
        path = found[0]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read()
    return from_profile(jax.profiler.ProfileData.from_serialized_xspace(raw))
