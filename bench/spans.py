"""Host spans the benchmark records around the calls it makes.

Each span is a ``jax.profiler.TraceAnnotation``, so in a traced run it
lands in the profiler's host plane on the same clock as the device ops,
and the trace reduction can say what the host was doing in each idle gap.
"""
from __future__ import annotations

import contextlib

import jax


class Spans:
    """Names every span the run opens, so the reduction knows which host
    events are the benchmark's own."""

    def __init__(self):
        self.names: set[str] = set()

    @contextlib.contextmanager
    def span(self, name: str):
        self.names.add(name)
        with jax.profiler.TraceAnnotation(name):
            yield
