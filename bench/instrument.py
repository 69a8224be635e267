"""Host-side instruments of a run: spans around the calls into each layer
of the program, which the trace reduction finds on the device's
timeline, and a count of JAX's compiles."""
from __future__ import annotations

import contextlib

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
SPAN_PREFIX = "bench."


class CompileClock:
    """Backend compiles (a persistent-cache read counts as one), from
    ``jax.monitoring``.  Listeners cannot be removed, so make one per
    process."""

    def __init__(self):
        self.compiles = []          # (start, end) host seconds
        jax.monitoring.register_event_time_span_listener(self._span)

    def _span(self, event, start, end, **_):
        if event == COMPILE_EVENT:
            self.compiles.append((start, end))

    def count(self) -> int:
        return len(self.compiles)


class Spans:
    """Named spans around the calls into each layer of the program, written
    into the profile as ``bench.<name>`` annotations while it records."""

    def __init__(self):
        self.annotate = False

    def span(self, name: str):
        if self.annotate:
            return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        return contextlib.nullcontext()
