"""The program's trace names: host spans and device scopes, all ``dawn.*``.

Two kinds of name, both written into the JAX profiler's own trace, so
they share its clock with the device's operations:

  * **spans** mark host work.  Each is a ``jax.profiler.TraceAnnotation``
    that lands on the calling thread's line of the host plane; its
    keyword arguments are recorded as the event's stats.  When no
    profiler records, a span costs a fraction of a microsecond, so the
    spans are always on.
  * **scopes** mark device work.  Each is a ``jax.named_scope`` that
    prefixes the ``op_name`` of every operation traced inside it; the
    compiled program carries the name as metadata (the profiler's
    ``tf_op`` stat of each device operation) and runs exactly as without
    it.

To see them, trace a window of your own process::

    import jax
    jax.profiler.start_trace("/tmp/dawn-trace")
    ...                      # h.apsp(...), svc.submit(...), svc.tick()
    jax.profiler.stop_trace()

and open the directory in TensorBoard's or XProf's profile viewer, or
read the ``.xplane.pb`` under it with ``jax.profiler.ProfileData``.

Spans (arguments in brackets):

  dawn.apsp               ``DawnGraph.apsp``: one facade call
                          [semiring, n_sources]
  dawn.engine.plan        the engine's set-up before its first tile:
                          tuning overlay, kernel/direction/fused
                          resolution, operand selection (a lazy dense,
                          packed or row operand build lands here)
                          [sparse_layout: ``rows`` where the sparse
                          form can run (its destination rows,
                          ``sweep.dst_rows``), ``none`` where a pinned
                          push or pull leaves it out; with ``rows``:
                          rows, the layout's R, and lane_fill, real
                          lanes over R x W; always table_bytes, the
                          (tile, n_pad) int8 frontier table,
                          gather_words, the uint32 words a vertex the
                          sparse form gathers from the frontier packed
                          32 keys a word (0 where it gathers the bytes,
                          under ``sweep.PACK_GATHER_BYTES``),
                          gather_table_bytes, the table it gathers
                          from, and operand_bytes, the operands the
                          plan resolved, dummies included: all from
                          shapes alone]
  dawn.engine.tile        one source tile: padding, upload, the batch
                          program's dispatch, the result slice
                          [valid: live rows, tile: rows of the program]
  dawn.engine.collect     ``apsp_engine``'s counter aggregation and row
                          concatenation
  dawn.serve.submit       ``GraphService.submit``: validation, row cache,
                          oracle, bucket
  dawn.serve.tick         ``GraphService.tick``: the ripeness scan and
                          the flush it starts
  dawn.serve.flush        one sweep flush of live queries [rows: live
                          queries, tile: the engine's source tile,
                          wait_ms: flush start minus the oldest live
                          query's submit, on the service's clock]
  dawn.serve.flush.wait   waiting for the device to finish the rows
  dawn.serve.flush.copy   the rows' copy from device to host
  dawn.serve.flush.fill   answering each query from its row and caching
                          the row

Scopes (device operations):

  dawn.sweep.<form>       one sweep in that form: push, pull, sparse,
                          dense (tropical / sharded dense), min_label, or
                          ``form`` for a closure of another name
  dawn.sweep.fused        a fused multi-sweep kernel block
  dawn.sweep.choose       the per-sweep direction choice
  dawn.sweep.test         the Fact-1 convergence test and the
                          ``edges_touched`` sum
  dawn.batch.init         the batch engine's initial frontier and
                          distances
"""
from __future__ import annotations

import jax

PREFIX = "dawn."

SPANS = ("apsp", "engine.plan", "engine.tile", "engine.collect",
         "serve.submit", "serve.tick", "serve.flush", "serve.flush.wait",
         "serve.flush.copy", "serve.flush.fill")
FORMS = ("push", "pull", "sparse", "dense", "min_label", "form")
SCOPES = tuple(f"sweep.{f}" for f in FORMS) + (
    "sweep.fused", "sweep.choose", "sweep.test", "batch.init")
NAMES = tuple(PREFIX + n for n in SPANS + SCOPES)


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Host span ``dawn.<name>``; ``args`` are recorded as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def spanned(name: str):
    """Decorator: each call of the function runs inside ``span(name)``."""
    return lambda fn: jax.profiler.annotate_function(fn, PREFIX + name)


def scope(name: str):
    """Device scope ``dawn.<name>`` over the operations traced inside."""
    return jax.named_scope(PREFIX + name)


def form_name(form) -> str:
    """The form a sweep closure implements, from its ``__name__``
    (``sparse_ref`` and ``sparse_form`` are ``sparse``)."""
    name = getattr(form, "__name__", "")
    for f in FORMS:
        if name == f or name.startswith(f + "_"):
            return f
    return "form"


def scoped_form(form):
    """``form`` with every operation it traces under
    ``dawn.sweep.<form_name(form)>``."""
    name = "sweep." + form_name(form)

    def run(*args):
        with scope(name):
            return form(*args)

    return run
