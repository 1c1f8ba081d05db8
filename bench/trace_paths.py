"""What a profiler trace says beyond ``bench.trace_reduce``: the program's
stage scopes and its host spans.

* Each device op's event metadata on a ``/device:*`` plane carries a
  ``tf_op`` stat, ``<path>:<type>``, whose path is the op's name stack,
  such as ``jit(run_scan)/while/body/closed_call/fl_train/.../dot_general``
  (ops the compiler adds, such as layout copies, carry none).
  ``jax.profiler.ProfileData`` does not expose metadata stats, so
  :func:`read_op_paths` walks the ``.xplane.pb`` wire format itself.
* :func:`path_seconds` gives device self time by path over the same
  clipped intervals ``bench.trace_reduce.reduce_events`` gives
  ``op_seconds`` by op name, so the two sum to the same time.  A loop the
  compiler emits without a path takes the path its body's ops share.
* A stage scope (``jax.named_scope``) is found in a path as a whole
  segment, possibly wrapped in transforms (``transpose(jvp(step_grad))``);
  an op belongs to the innermost stage on its path.
* The program's host spans (``repro.obs.trace``) are
  ``TraceAnnotation``s on the device trace's clock: :func:`span_idle`
  puts each stretch of device idle time under the innermost program span
  over it.

``python3 bench/stage_report.py`` runs a cell under the profiler and
prints all of this per unit of the cell.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench.trace_reduce import (OPS_LINE, WINDOW_SPAN, leaf_ops, self_times,
                                union_length)

#: the round engine's stage scopes (``repro.fl.engine``), in round order
FL_STAGES = ("fl_select", "fl_gather", "fl_train", "fl_aggregate",
             "fl_evaluate", "fl_observe")
#: the gated step's stage scopes (``repro.dist.gpfl_step``)
STEP_STAGES = ("gpfl_score", "gpfl_gate", "step_grad", "step_update")
#: the program spans ``ScanEngine.run()`` opens around one dispatch
RUN_SPANS = ("copy_inputs", "scan_dispatch", "fetch_outputs",
             "build_result")
#: the host work of ``run()`` around the device's part of a dispatch
HOST_SPANS = ("copy_inputs", "fetch_outputs", "build_result")

_WRAP = re.compile(r"^[A-Za-z_][\w.]*\((.*)\)$")


# ------------------------------------------------------------ wire format

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint or fixed field, a ``memoryview`` for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, val


def _map_entry(buf):
    """``(key, value)`` of a protobuf map entry (fields 1 and 2)."""
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane_paths(plane):
    """``(plane name, [tf_op path of each event of its ops line])``.

    XPlane: 2 name, 3 lines, 4 event_metadata, 5 stat_metadata.  XLine:
    2 name, 4 events.  XEvent: 1 metadata_id.  XEventMetadata: 5 stats.
    XStat: 1 metadata_id, 5 str_value, 7 ref_value (a stat metadata id
    whose name is the string).  XStatMetadata: 2 name."""
    name, ops_line, ev_meta, stat_names = "", None, {}, {}
    for f, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
        elif f == 3 and ops_line is None and name.startswith("/device:"):
            events = []
            for lf, lv in _fields(v):
                if lf == 2 and bytes(lv).decode() != OPS_LINE:
                    break  # the name precedes the events
                if lf == 4:
                    events.append(lv)
            else:
                ops_line = events
        elif f == 4:
            key, val = _map_entry(v)
            ev_meta[key] = val
        elif f == 5:
            key, val = _map_entry(v)
            stat_names[key] = next((bytes(x).decode() for g, x in
                                    _fields(val) if g == 2), "")
    if not name.startswith("/device:") or ops_line is None:
        return name, None
    tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    path_of = {}
    for mid, meta in ev_meta.items():
        path = ""
        for f, st in _fields(meta):
            if f != 5:
                continue
            sid = sval = None
            for g, x in _fields(st):
                if g == 1:
                    sid = x
                elif g == 5:
                    sval = bytes(x).decode()
                elif g == 7:
                    sval = stat_names.get(x, "")
            if sid == tf_op and sval is not None:
                path = sval.rpartition(":")[0] or sval  # "<path>:<type>"
        path_of[mid] = path
    paths = []
    for ev in ops_line:
        mid = next((v for f, v in _fields(ev) if f == 1), None)
        paths.append(path_of.get(mid, ""))
    return name, paths


def read_op_paths(path: str) -> dict:
    """``{device plane name: [path, ...]}`` from one ``.xplane.pb``: the
    ``tf_op`` path of each event of the plane's ops line, in the order
    ``jax.profiler.ProfileData`` lists them (``""`` where an event has
    none), for the planes ``bench.trace_reduce.read_xplane`` reads."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, paths = _plane_paths(plane)
        if paths is not None and "CPU" not in name:
            out[name] = paths
    return out


# ---------------------------------------------------------------- reduce

def window_of(host_spans):
    """``(start_ns, end_ns)`` of the benchmark's ``window`` span."""
    wins = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError("the trace holds no 'window' span")
    return wins[0]


def _common_prefix(paths) -> str:
    """The leading path segments that all of ``paths`` share."""
    segs = [p.split("/") for p in set(paths)]
    out = []
    for parts in zip(*segs):
        if any(x != parts[0] for x in parts):
            break
        out.append(parts[0])
    return "/".join(out)


def loop_paths(ops, paths) -> list:
    """``paths`` with each path-less op that contains others given the
    path the ops inside it share: the compiler emits loops (``while``)
    without an op name, and a loop's own time belongs to the scope of
    its body, or to none where its body spans several stages."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    inner, stack = defaultdict(list), []
    for i in order:
        while stack and ops[i][1] >= ops[stack[-1]][2]:
            stack.pop()
        if paths[i]:
            for j in stack:
                if not paths[j]:
                    inner[j].append(paths[i])
        stack.append(i)
    out = list(paths)
    for j, inside in inner.items():
        out[j] = _common_prefix(inside)
    return out


def path_seconds(device_ops: dict, op_paths: dict, lo, hi) -> dict:
    """``{path: device self seconds, per device}`` over ``[lo, hi]``:
    the intervals and self times ``reduce_events`` sums by op name, a
    path-less loop taking the path of its body (:func:`loop_paths`)."""
    n_dev = max(len(device_ops), 1)
    out = defaultdict(float)
    for dev, ops in device_ops.items():
        given = op_paths.get(dev, [])
        paths = loop_paths(ops, [given[i] if i < len(given) else ""
                                 for i in range(len(ops))])
        inside = [(paths[i], max(s, lo), min(e, hi))
                  for i, (_, s, e) in enumerate(ops)
                  if min(e, hi) > max(s, lo)]
        for path, ns in self_times(inside):
            out[path] += ns
    return {k: v / n_dev * 1e-9 for k, v in out.items()}


def has_scope(path: str, name: str) -> bool:
    """Whether ``name`` is a whole segment of ``path``, bare or wrapped in
    transforms (``jvp(name)``, ``transpose(jvp(name))``)."""
    for seg in path.split("/"):
        while True:
            if seg == name:
                return True
            m = _WRAP.match(seg)
            if not m:
                break
            seg = m.group(1)
    return False


def innermost(path: str, names) -> str | None:
    """The last segment of ``path`` that is one of ``names`` (bare or
    wrapped in transforms), or ``None``."""
    for seg in reversed(path.split("/")):
        for name in names:
            if has_scope(seg, name):
                return name
    return None


def stage_seconds(paths: dict, stages) -> dict:
    """``{stage: seconds}`` of ``paths`` (``{path: seconds}``), each path
    under its innermost stage; the time under none is keyed ``None``."""
    out = {s: 0.0 for s in stages}
    out[None] = 0.0
    for path, secs in paths.items():
        out[innermost(path, stages)] += secs
    return out


def scope_seconds(paths: dict, name: str) -> float:
    """Seconds of the paths that hold scope ``name`` anywhere."""
    return sum(s for p, s in paths.items() if has_scope(p, name))


def idle_intervals(device_ops: dict, lo, hi) -> list:
    """``[(start_ns, end_ns)]`` in which a device ran no leaf op, each
    device's in turn (the gaps ``reduce_events`` labels)."""
    out = []
    for ops in device_ops.values():
        _, merged = union_length([(s, e) for _, s, e in leaf_ops(ops)],
                                 lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        out.extend((g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                   if g1 > g0)
    return out


def span_idle(gaps, host_spans, names) -> dict:
    """``{span name: idle seconds}``: each stretch of ``gaps`` put under
    the innermost (shortest) span of ``names`` over it, ``None`` where
    none is.  A gap is cut where a span starts or ends, so one gap may
    fall under several spans."""
    spans = [(n, s, e) for n, s, e in host_spans if n in names]
    out = defaultdict(float)
    for g0, g1 in gaps:
        cuts = sorted({g0, g1} | {x for _, s, e in spans for x in (s, e)
                                  if g0 < x < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            over = [(e - s, n) for n, s, e in spans if s <= mid <= e]
            out[min(over)[1] if over else None] += (b - a) * 1e-9
    return dict(out)


def largest_gaps(gaps, host_spans, names, top: int = 5) -> list:
    """The ``top`` longest idle gaps as ``[(seconds, {span: seconds})]``."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return [((g1 - g0) * 1e-9, span_idle([(g0, g1)], host_spans, names))
            for g0, g1 in longest]


def reduce_paths(device_ops: dict, host_spans: list, op_paths: dict) -> dict:
    """The keys a reader of stage scopes and program spans needs:
    ``path_seconds`` ({path: self seconds per device}), ``host_spans``
    (the host spans that overlap the window, ``[(name, start_ns,
    end_ns)]``), ``idle_intervals`` (``[(start_ns, end_ns)]``) and
    ``n_devices``."""
    lo, hi = window_of(host_spans)
    return {
        "path_seconds": path_seconds(device_ops, op_paths, lo, hi),
        "host_spans": [(n, s, e) for n, s, e in host_spans
                       if e > lo and s < hi and n != WINDOW_SPAN],
        "idle_intervals": idle_intervals(device_ops, lo, hi),
        "n_devices": len(device_ops),
    }


# --------------------------------------------------------------- metrics

def fl_stage_us(red: dict, rounds: int) -> dict:
    """Device self time per round under each ``fl_*`` stage, in µs
    (``{}`` where the trace holds no stage scope)."""
    st = stage_seconds(red["path_seconds"], FL_STAGES)
    if not rounds or not any(st[s] for s in FL_STAGES):
        return {}
    return {s[3:]: 1e6 * st[s] / rounds for s in FL_STAGES}


def step_stage_ms(red: dict, steps: int) -> dict:
    """Device self time per step under each gated-step stage, in ms, and
    under ``ssd_inter_chunk`` wherever it is nested (``{}`` without
    stage scopes)."""
    st = stage_seconds(red["path_seconds"], STEP_STAGES)
    if not steps or not any(st[s] for s in STEP_STAGES):
        return {}
    out = {s.split("_", 1)[1]: 1e3 * st[s] / steps for s in STEP_STAGES}
    out["ssd_inter_chunk"] = 1e3 * scope_seconds(
        red["path_seconds"], "ssd_inter_chunk") / steps
    return out


def unscoped_share(red: dict, stages) -> float | None:
    """Share of device self time (%) under none of ``stages``; ``None``
    where no op is under any of them."""
    st = stage_seconds(red["path_seconds"], stages)
    total = sum(st.values())
    if total <= 0 or st[None] >= total:
        return None
    return 100.0 * st[None] / total


def host_idle_ms_per_dispatch(red: dict) -> float | None:
    """Device idle time whose innermost ``run()`` span is host work
    (``copy_inputs``, ``fetch_outputs``, ``build_result``), in ms per
    ``scan_dispatch`` span in the window; ``None`` where the trace holds
    none."""
    dispatches = sum(n == "scan_dispatch" for n, _, _ in red["host_spans"])
    if not dispatches:
        return None
    idle = span_idle(red["idle_intervals"], red["host_spans"], RUN_SPANS)
    per_dev = sum(idle.get(n, 0.0) for n in HOST_SPANS) \
        / max(red["n_devices"], 1)
    return 1e3 * per_dev / dispatches
