"""What every run shares: the context a traffic driver runs in, and the
outcome it hands back.

A driver (``bench/drivers/<name>.py``, named by the traffic file's
``driver`` key) builds the configuration's graph, warms up, calls
``ctx.setup_done()``, runs its window inside ``with ctx.window():``,
reads ``ctx.memory_peak()``, frees the program's state and only then
runs the reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import shutil
import sys
import tempfile
import time
from typing import Optional

import jax
import numpy as np

from bench import trace as trace_mod
from bench.instrument import CompileClock, Spans

# numpy streams, one for each use: ``Context.rng`` draws them from the
# run's --seed, ``Context.job_rng`` from the configuration's graph_seed
STREAM_KEYS, STREAM_WARM, STREAM_CHECK, STREAM_ARRIVALS = 1, 2, 3, 4


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    metrics: dict           # end-to-end metric name -> value
    checks: dict            # compared number -> (value, limit)
    counters: dict          # what per-layer readers read besides the trace
    memory_peak_bytes: int


@dataclasses.dataclass
class Context:
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # host clock at process start
    options: dict = dataclasses.field(default_factory=dict)
    keep_trace: Optional[str] = None
    spans: Spans = dataclasses.field(default_factory=Spans)
    clock: Optional[CompileClock] = None
    setup_s: Optional[float] = None
    compiles_in_window: Optional[int] = None
    reduced: Optional[trace_mod.Trace] = None

    @property
    def graph_seed(self) -> int:
        """The configuration's fixed seed: a deployment serves one graph,
        and ``--seed`` varies only the traffic on it."""
        seed = self.config.get("graph_seed")
        if type(seed) is not int or seed < 0:
            raise ValueError(
                f"configuration {self.config['name']!r} needs a "
                f"graph_seed, a whole number >= 0; it has {seed!r}")
        return seed

    def rng(self, stream: int) -> np.random.Generator:
        """The traffic's numpy stream ``stream`` of the run's ``--seed``."""
        return np.random.default_rng([self.seed, stream])

    def job_rng(self, stream: int) -> np.random.Generator:
        """Numpy stream ``stream`` of the configuration's ``graph_seed``:
        what the deployment fixes beside its graph, such as the batches
        of a job."""
        return np.random.default_rng([self.graph_seed, stream])

    def graph(self):
        """The configuration's one graph, built from its ``graph_seed``."""
        gen = importlib.import_module(
            f"bench.generators.{self.config['generator']}")
        g = gen.build(self.graph_seed, self.config["graph"])
        print(f"graph: {g.n_nodes} vertices, {g.n_edges} lanes, m_pad "
              f"{g.m_pad}", file=sys.stderr)
        return g

    def facade_options(self) -> dict:
        """``repro.prepare`` keywords: the configuration's, then any
        override (the control run caps ``max_steps`` here)."""
        return {**self.config["facade"], **self.options}

    @property
    def window_seconds(self) -> float:
        """The measured window; a traced run traces a shorter one."""
        if self.trace:
            return min(self.seconds, self.traffic["trace_seconds"])
        return self.seconds

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    @contextlib.contextmanager
    def window(self):
        """The measured window: compiles inside it are counted; with
        ``trace`` the profiler records it and it is reduced on exit."""
        before = self.clock.count() if self.clock else 0
        tmp = None
        if self.trace:
            tmp = self.keep_trace or tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tmp, profiler_options=opts)
            self.spans.annotate = True
        try:
            with self.spans.span(trace_mod.WINDOW):
                yield
        finally:
            if self.trace:
                jax.profiler.stop_trace()
                self.spans.annotate = False
        self.compiles_in_window = (self.clock.count() if self.clock
                                   else 0) - before
        if self.trace:
            self.reduced = trace_mod.load(tmp)
            if self.keep_trace is None:
                shutil.rmtree(tmp, ignore_errors=True)

    @staticmethod
    def memory_peak() -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return int(max(peaks))
