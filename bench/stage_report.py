#!/usr/bin/env python3
"""Run one cell under the profiler and print where its device time goes by
the program's stage scopes, and its idle time by the program's spans.

    python3 bench/stage_report.py --workload <cell> --seed <n>
        [--keep FILE] [--rounds N] [--seq N]

Set-up is the benchmark's own (``bench/run.py``); the traced window is the
cell's first ``trace_units`` units inside the benchmark's ``window`` span.
The last line of standard output is a JSON object: the window, the device
self time per unit (the sum of ``op_seconds``), the self time of each stage
per unit and the share under none, ``ssd_inter_chunk`` time per step,
device idle time by the innermost program span, the longest gaps and the
paths that take the most time.  ``--keep FILE`` keeps the ``.xplane.pb``;
``--rounds`` cuts an FL cell's dispatch to N rounds and ``--seq`` sets a
gated cell's sequence length.
No outputs are compared: this is a measurement, not a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace_paths as tp  # noqa: E402
from bench.run import (TRACE_DIR, devices_for, driver_class,  # noqa: E402
                       enable_cache, load_cell)
from bench.spans import Spans  # noqa: E402
from bench.trace_reduce import (find_xplane, read_xplane,  # noqa: E402
                                reduce_events)


def report(device_ops, host_spans, op_paths, *, unit: str, units: int,
           steps: int, span_names) -> dict:
    """The stage and span breakdown of a traced window of ``steps`` calls
    (FL dispatches or train steps) holding ``units`` units."""
    from repro.obs.trace import SPAN_NAMES
    red = reduce_events(device_ops, host_spans, span_names)
    paths = tp.reduce_paths(device_ops, host_spans, op_paths)
    self_s = sum(red["op_seconds"].values())
    names = set(SPAN_NAMES)
    out = {
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "idle_share": red["idle_share"], "steps": steps, unit: units,
        "self_ms_per_step": 1e3 * self_s / steps,
        "idle_ms_by_span": {str(k): 1e3 * v for k, v in tp.span_idle(
            paths["idle_intervals"], paths["host_spans"], names).items()},
        "largest_gaps_ms": [
            [1e3 * s, {str(k): 1e3 * v for k, v in by.items()}]
            for s, by in tp.largest_gaps(paths["idle_intervals"],
                                         paths["host_spans"], names)],
        "top_paths_ms": [[1e3 * s, p] for p, s in sorted(
            paths["path_seconds"].items(), key=lambda kv: -kv[1])[:12]],
    }
    if unit == "rounds":
        out["fl_stage_us"] = tp.fl_stage_us(paths, units)
        out["unscoped_share.fl"] = tp.unscoped_share(paths, tp.FL_STAGES)
        out["host_idle_ms_per_dispatch"] = tp.host_idle_ms_per_dispatch(
            paths)
    else:
        out["step_stage_ms"] = tp.step_stage_ms(paths, steps)
        out["ssd_intra_chunk_ms"] = 1e3 * tp.scope_seconds(
            paths["path_seconds"], "ssd_intra_chunk") / steps
        out["unscoped_share.gated"] = tp.unscoped_share(paths,
                                                        tp.STEP_STAGES)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--keep", help="copy the .xplane.pb here")
    ap.add_argument("--rounds", type=int, help="FL rounds a dispatch")
    ap.add_argument("--seq", type=int, help="gated sequence length")
    args = ap.parse_args(argv)

    import jax
    wl, cfg = load_cell(args.workload)
    if args.rounds:
        cfg["fl"]["rounds"] = args.rounds
    if args.seq:
        wl["train"]["seq"] = args.seq
    devices_for(wl["chips"])
    enable_cache()
    # the cache's key leaves op metadata out by default, so a program that
    # differs from a cached one only in its scopes would load the cached
    # executable, and its trace would show the other build's op names
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    drv = driver_class(wl["driver"])(cfg, wl, args.seed)
    drv.setup()

    spans = Spans()
    units = steps = 0
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    TRACE_DIR.mkdir(parents=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    with spans.span("window"):
        while steps < wl["trace_units"]:
            units += drv.step(spans)
            steps += 1
        drv.drain(spans)
    jax.profiler.stop_trace()
    xplane = find_xplane(str(TRACE_DIR))
    if args.keep:
        Path(args.keep).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(xplane, args.keep)
    t0 = time.perf_counter()
    device_ops, host_spans = read_xplane(xplane)
    out = report(device_ops, host_spans, tp.read_op_paths(xplane),
                 unit=drv.unit, units=units, steps=steps,
                 span_names=spans.names)
    out.update(cell=args.workload, seed=args.seed,
               reduce_s=time.perf_counter() - t0)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
