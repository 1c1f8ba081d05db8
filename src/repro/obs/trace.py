"""Host-side span tracer → Chrome/Perfetto trace-event JSON.

The ``telemetry="trace"`` path wraps the engine's host-visible boundaries —
jit dispatches, ``device_put`` slab uploads in the streamed pre-selection
path, snapshot writes — in :meth:`SpanTracer.span` blocks.  Spans are
recorded as Chrome trace-event "X" (complete) events, so the saved JSON
loads directly in ``chrome://tracing`` / Perfetto.

Scope note: spans deliberately measure *host* time (dispatch + blocking
waits), not device time.  For device-side profiles the bench lane can opt
into :func:`profiler_capture`, a thin wrapper over ``jax.profiler``'s
programmatic capture API.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional

#: Chrome trace-event phase codes this module emits / validates.
TRACE_PHASES = ("X", "i", "M")


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
        """Context manager recording one complete ("X") event around a block.

        Keyword ``args`` land in the event's ``args`` payload (must be
        JSON-serialisable; keep them small — round indices, byte counts).
        """
        t0 = self._now_us()
        try:
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

    def instant(self, name: str, **args) -> None:
        """Record a zero-duration instant ("i") event (e.g. a retry mark)."""
        ev = {
            "name": name, "ph": "i", "ts": self._now_us(), "s": "t",
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
    """No-op stand-in so call sites can write ``tracer.span(...)`` unconditionally."""

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Yield immediately; records nothing."""
        yield self

    def instant(self, name: str, **args) -> None:
        """Records nothing."""

    def to_dict(self) -> dict:
        """An empty (but schema-valid) trace object."""
        return {"traceEvents": [], "displayTimeUnit": "ms"}


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


@contextlib.contextmanager
def profiler_capture(logdir: Optional[str]):
    """Opt-in ``jax.profiler`` programmatic capture around a block.

    ``logdir=None`` (the default everywhere outside the bench lane) is a
    no-op.  A trace that cannot start or stop raises: a run asked to be
    profiled does not silently come back without its trace.
    """
    if not logdir:
        yield
        return
    import jax
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def tracer_for(telemetry: str):
    """The tracer matching a telemetry mode: real for "trace", null otherwise."""
    return SpanTracer() if telemetry == "trace" else NullTracer()


#: Re-exported for callers that only need type names.
__all__ = [
    "NullTracer",
    "SpanTracer",
    "TRACE_PHASES",
    "profiler_capture",
    "tracer_for",
    "validate_trace",
]
