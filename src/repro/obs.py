"""Spans and counters: where the program's own time goes.

The program's one recorder. Layers open spans at their boundaries and bump
counters where work happens; an operator or a benchmark reads the sums:

    with obs.span("dedup.add_batch", batch=7):
        with obs.span("dedup.sign"):
            ...
    obs.count("stream.dispatches")
    obs.totals()  # {"spans": {path: {count, total_s, self_s}},
                  #  "counters": {name: n}}

A span is recorded under its *path*, the names of the spans open around it
joined by ``/`` (``dedup.add_batch/dedup.sign/dedup.sign.fetch``). Per path
the recorder sums the count, the total time and the time that child spans
covered, so a layer's self time is its total less its children's. Times
come from ``time.perf_counter_ns``.

Every span is also a ``jax.profiler.TraceAnnotation`` with the span's name
and arguments: under a profiler session it appears on a host plane of the
trace, on the trace's clock, beside the device's operations. Without one
the annotation costs about a microsecond. Nothing else switches the
recorder on or off, and only the sums are kept, so memory is bounded by
the number of distinct paths and counters; one record per event exists only
in a profiler trace.

State is context-local. Each path and each counter is a
``contextvars.ContextVar`` holding an immutable value, so a copied context
(``contextvars.copy_context``, an asyncio task) starts from the sums it was
copied with and its own updates stay its own. A thread starts with an empty
context: spans opened in a worker thread do not nest under the caller's
path, so open them in the calling thread.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from typing import Dict, Iterator

import jax

__all__ = ["span", "count", "counter", "totals"]

# path of the innermost open span in this context ("" outside every span)
_path = contextvars.ContextVar("repro.obs.path", default="")
# path -> ContextVar of (count, total_ns, child_ns); name -> ContextVar of n.
# The tables only name the variables; every value lives in the context.
_spans: Dict[str, contextvars.ContextVar] = {}
_counters: Dict[str, contextvars.ContextVar] = {}
_lock = threading.Lock()


def _var(table: Dict[str, contextvars.ContextVar], kind: str,
         key: str) -> contextvars.ContextVar:
    var = table.get(key)
    if var is None:
        with _lock:
            var = table.get(key)
            if var is None:
                var = table[key] = contextvars.ContextVar(
                    f"repro.obs.{kind}:{key}")
    return var


def _add(path: str, n: int, total_ns: int, child_ns: int) -> None:
    var = _var(_spans, "span", path)
    c, t, ch = var.get((0, 0, 0))
    var.set((c + n, t + total_ns, ch + child_ns))


@contextlib.contextmanager
def span(name: str, **args) -> Iterator[None]:
    """Time the block under ``name``, nested under the spans open around it;
    ``args`` go to the trace annotation only (an identifier such as a batch
    ordinal, so the spans of one unit of work can be matched in a trace)."""
    parent = _path.get()
    path = f"{parent}/{name}" if parent else name
    token = _path.set(path)
    t0 = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield
    finally:
        dt = time.perf_counter_ns() - t0
        _path.reset(token)
        _add(path, 1, dt, 0)
        if parent:
            _add(parent, 0, 0, dt)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` in this context."""
    var = _var(_counters, "counter", name)
    var.set(var.get(0) + n)


def counter(name: str) -> int:
    """The counter ``name`` in this context (0 if never counted)."""
    var = _counters.get(name)
    return 0 if var is None else var.get(0)


def totals() -> dict:
    """A snapshot of this context's sums: ``{"spans": {path: {"count",
    "total_s", "self_s"}}, "counters": {name: n}}``. A span still open is
    left out until it closes (its closed children are in)."""
    with _lock:
        spans, counters = list(_spans.items()), list(_counters.items())
    out_spans = {}
    for path, var in spans:
        c, t, ch = var.get((0, 0, 0))
        if c:
            out_spans[path] = {"count": c, "total_s": t * 1e-9,
                               "self_s": (t - ch) * 1e-9}
    out_counters = {}
    for name, var in counters:
        n = var.get(None)
        if n is not None:
            out_counters[name] = n
    return {"spans": out_spans, "counters": out_counters}
