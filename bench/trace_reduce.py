"""Reduce a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read.

* busy time: the union of the intervals in which an operation ran on a
  device (overlapping ops are counted once), inside the benchmark's
  ``window`` span, averaged over the devices.  An op that contains
  others (a ``while`` loop over its body's ops) counts only through
  them, so a gap between the ops inside a loop is idle;
* idle share: 1 − busy / window;
* device time per op name, summed over its events: self time, since
  an op that contains others (a ``while`` loop) covers their intervals;
* the idle gaps between busy intervals, each labelled with the innermost
  benchmark host span that covers its midpoint.

Device planes are named ``/device:<KIND>:<n>``; their ops are on the line
named ``XLA Ops``, each event named by its HLO instruction text
(``%fusion.12 = f32[...] fusion(...), ...``), which is cut to the
instruction's name (``fusion.12``).  Host spans are events of the host
plane's lines.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"


def union_length(intervals, lo: float, hi: float):
    """Merge ``(start, end)`` intervals clipped to ``[lo, hi]`` →
    ``(covered length, merged list)``."""
    ivs = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                 if e > lo and s < hi)
    merged = []
    for s, e in ivs:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def op_name(text: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` → ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def leaf_ops(ops):
    """The ops in which no other op starts: a ``while`` op, whose
    interval covers its body's ops, is left out."""
    srt = sorted(ops, key=lambda o: (o[1], -o[2]))
    parent, stack = set(), []  # stack of indices into srt
    for i, (_, s, _e) in enumerate(srt):
        while stack and s >= srt[stack[-1]][2]:
            stack.pop()
        if stack:
            parent.add(stack[-1])
        stack.append(i)
    return [o for i, o in enumerate(srt) if i not in parent]


def self_times(ops):
    """``[(name, self ns)]`` of intervals that may nest: each op's
    duration less that of the ops directly inside it."""
    out, stack = [], []  # stack of [end, index into out]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([name, e - s])
        stack.append([e, len(out) - 1])
    return out


def label_gap(mid: float, spans) -> str:
    """The innermost (shortest) span covering ``mid``; ``"none"`` else."""
    best, best_len = "none", None
    for name, s, e in spans:
        if s <= mid <= e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce_events(device_ops: dict, host_spans: list,
                  span_names=None) -> dict:
    """The reduction proper, on plain data (testable without a trace).

    Args:
      device_ops: ``{device name: [(op name, start_ns, end_ns), ...]}``.
      host_spans: ``[(span name, start_ns, end_ns), ...]``; one of them
        must be the ``window`` span.
      span_names: the benchmark's span names that may label a gap
        (``None``: any host span).

    Returns a dict with ``window_s``, ``busy_s`` (mean over devices),
    ``idle_share`` (0..1), ``op_seconds`` and ``op_counts`` ({name:
    seconds or events, per device}), ``gaps`` ([(label, s)] longest first) and
    ``n_devices``.
    """
    wins = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = wins[0]
    spans = [(n, s, e) for n, s, e in host_spans
             if n != WINDOW_SPAN and (span_names is None or n in span_names)]
    n_dev = max(len(device_ops), 1)
    busy = 0.0
    op_ns = defaultdict(float)
    op_n = defaultdict(int)
    gaps = []
    for dev, ops in device_ops.items():
        covered, merged = union_length(
            [(s, e) for _, s, e in leaf_ops(ops)], lo, hi)
        busy += covered
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if min(e, hi) > max(s, lo)]
        for name, ns in self_times(inside):
            op_ns[name] += ns
            op_n[name] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((label_gap(0.5 * (g0 + g1), spans),
                             (g1 - g0) * 1e-9))
    window_s = (hi - lo) * 1e-9
    busy_s = busy / n_dev * 1e-9
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else 0.0,
        "op_seconds": {k: v / n_dev * 1e-9 for k, v in op_ns.items()},
        "op_counts": {k: v / n_dev for k, v in op_n.items()},
        "gaps": gaps,
        "n_devices": len(device_ops),
    }


def read_xplane(path: str):
    """``(device_ops, host_spans)`` from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, host_spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = [
                        (op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                                  for e in line.events)
    return device_ops, host_spans


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` output dir."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def reduce_trace(trace_dir: str, span_names=None) -> dict:
    """Read the trace under ``trace_dir`` and reduce it."""
    device_ops, host_spans = read_xplane(find_xplane(trace_dir))
    if not device_ops:
        raise ValueError(f"the trace under {trace_dir} has no device ops")
    return reduce_events(device_ops, host_spans, span_names)
