"""Named spans of the program's own work, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named ``mcm:<name>``: while
a profiler trace runs it lands on the trace's host plane, on the clock
the device planes use, with its keyword arguments as event stats; with
no trace running it costs about a microsecond. Counts travel as span
arguments (``span("sweep.lookup")`` then ``set_metadata(hits=...)`` on
the object the ``with`` statement yields).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

PREFIX = "mcm:"


def span(name: str, **args) -> TraceAnnotation:
    """A context manager spanning the program's work ``name``."""
    return TraceAnnotation(PREFIX + name, **args)
