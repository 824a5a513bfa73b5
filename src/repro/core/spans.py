"""Host spans on the profiler's clock, and the phase clock of a query.

``span("run")`` is ``jax.profiler.TraceAnnotation("hf.run")``: while a
profiler trace is active (``jax.profiler.trace(dir)``) it writes an
``hf.run`` event on the calling thread's line of the trace's host plane,
the same ``.xplane.pb`` that holds the device ops, on the same clock.
With no trace active it records nothing: one check, and keyword arguments
(``span("query", qid=7)``) are formatted only while a trace is active.

``timed(name, acc)`` is such a span that also adds its seconds, on the
monotonic clock the serving session times queries with, to
``acc[name]``.  Span names are constants; docs/serving.md lists them.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

from jax.profiler import TraceAnnotation

MONO = time.monotonic
PREFIX = "hf."


def span(name: str, **args) -> TraceAnnotation:
    """The context manager of the host span ``hf.<name>``."""
    return TraceAnnotation(PREFIX + name, **args)


@contextmanager
def timed(name: str, acc: dict | None):
    """Span ``hf.<name>`` whose seconds are added to ``acc[name]`` (unless
    ``acc`` is None), also when the body raises."""
    t0 = MONO()
    try:
        with TraceAnnotation(PREFIX + name):
            yield
    finally:
        if acc is not None:
            acc[name] = acc.get(name, 0.0) + MONO() - t0
