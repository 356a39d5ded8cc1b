"""Host spans the harness records around calls into the program.

A span is the wall time of one call, summed per name. With ``annotate``
on (traced runs) each span is also a ``jax.profiler.TraceAnnotation``, so
the trace holds it on the host clock beside the device's operations, and
an idle gap of the device can be named by the span the host was in.
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import defaultdict


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.seconds = defaultdict(float)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.seconds[name] += time.perf_counter() - t0

    def wrap(self, obj, attr: str, name: str, keep=None) -> None:
        """Time every call of ``obj.attr`` under ``name``; ``keep(result)``
        sees each call's result. Set on the instance only: the program's
        code is unchanged."""
        fn = getattr(obj, attr)

        @functools.wraps(fn)
        def timed(*args, **kw):
            with self.span(name):
                out = fn(*args, **kw)
            if keep is not None:
                keep(out)
            return out

        setattr(obj, attr, timed)
