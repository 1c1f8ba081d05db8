"""Program spans: one API, on the profiler's clock and in Chrome JSON.

The engines wrap their host-visible boundaries — input copies, jit
dispatches, output fetches, ``device_put`` slab uploads in the streamed
pre-selection path, snapshot writes — in ``tracer.span(name)`` blocks.
Every span, whatever the telemetry mode, opens a
``jax.profiler.TraceAnnotation`` of the same name: it costs next to
nothing while no profiler runs, and inside a ``jax.profiler`` capture it
lands on the host plane on the same clock as the device ops, so a trace
can say what the host was doing in each idle gap of the device.

Under ``telemetry="trace"`` :class:`SpanTracer` also records each span as
a Chrome trace-event "X" (complete) event, so the saved JSON loads
directly in ``chrome://tracing`` / Perfetto.  Keyword args go only into
that event, never into the annotation's name.

Every span name the program opens is listed in :data:`SPAN_NAMES`.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import List

import jax

#: Chrome trace-event phase codes this module emits / validates.
TRACE_PHASES = ("X", "M")

#: Every span name the program opens (``ScanEngine``'s single, chunked
#: and buffered dispatch paths; the streamed pre-selection runner).
SPAN_NAMES = frozenset({
    # ScanEngine._run_single
    "copy_inputs", "scan_dispatch", "fetch_outputs", "build_result",
    # ScanEngine._run_chunked
    "prefill_dispatch", "chunk_dispatch", "snapshot_write",
    # repro.fl.preselect.run_pooled_stream
    "tier1_pool", "device_put_pool", "round_dispatch",
})


class SpanTracer:
    """Collects timed spans as Chrome trace-event dicts.

    Thread-safe append (the streamed path's prefetch may run off-thread);
    timestamps come from ``time.perf_counter_ns`` and are reported in the
    trace format's microseconds.
    """

    def __init__(self, process_name: str = "repro"):
        """Start an empty trace labelled ``process_name`` in the viewer."""
        self.events: List[dict] = []
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self.events.append({
            "name": "process_name", "ph": "M", "pid": self._pid, "tid": 0,
            "args": {"name": process_name},
        })

    @staticmethod
    def _now_us() -> float:
        """Monotonic timestamp in microseconds."""
        return time.perf_counter_ns() / 1e3

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """A profiler annotation plus one complete ("X") event around a
        block.

        Keyword ``args`` land in the event's ``args`` payload (must be
        JSON-serialisable; keep them small — round indices, byte counts).
        """
        t0 = self._now_us()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield self
        finally:
            t1 = self._now_us()
            ev = {
                "name": name, "ph": "X", "ts": t0, "dur": t1 - t0,
                "pid": self._pid, "tid": threading.get_ident() & 0xFFFF,
            }
            if args:
                ev["args"] = args
            with self._lock:
                self.events.append(ev)

    def to_dict(self) -> dict:
        """The trace as a Chrome trace-event JSON object."""
        with self._lock:
            return {"traceEvents": list(self.events),
                    "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        """Write the trace JSON to ``path`` (parent dirs created); returns it."""
        parent = os.path.dirname(os.path.abspath(path))
        os.makedirs(parent, exist_ok=True)
        with open(path, "w") as fh:
            json.dump(self.to_dict(), fh)
        return path


class NullTracer:
    """The tracer outside ``telemetry="trace"``: the profiler annotation
    alone, so call sites write ``tracer.span(...)`` unconditionally."""

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """The profiler annotation ``name``; ``args`` are dropped."""
        with jax.profiler.TraceAnnotation(name):
            yield self


def validate_trace(obj: dict) -> List[str]:
    """Validate ``obj`` against the Chrome trace-event schema (subset we emit).

    Returns a list of human-readable problems — empty means valid.  Checked:
    top-level ``traceEvents`` list; per-event required keys (``name``,
    ``ph``, ``pid``, ``tid``; ``ts`` for non-metadata events); known phase
    codes; non-negative ``dur`` on "X" events.
    """
    problems: List[str] = []
    events = obj.get("traceEvents")
    if not isinstance(events, list):
        return ["top-level 'traceEvents' missing or not a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event[{i}] is not an object")
            continue
        for key in ("name", "ph", "pid", "tid"):
            if key not in ev:
                problems.append(f"event[{i}] ({ev.get('name')!r}) missing "
                                f"required key {key!r}")
        ph = ev.get("ph")
        if ph not in TRACE_PHASES:
            problems.append(f"event[{i}] has unknown phase {ph!r}")
        if ph != "M" and "ts" not in ev:
            problems.append(f"event[{i}] ({ev.get('name')!r}) missing 'ts'")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                problems.append(f"event[{i}] 'X' event has bad dur={dur!r}")
    return problems


#: Re-exported for callers that only need type names.
__all__ = [
    "NullTracer",
    "SPAN_NAMES",
    "SpanTracer",
    "TRACE_PHASES",
    "validate_trace",
]
