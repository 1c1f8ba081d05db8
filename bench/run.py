#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``bench/workloads/<cell>.json``: its configuration
(``bench/configs/<config>.json``), the driver that runs it
(``bench/drivers/<driver>.py``) and that driver's parameters.  The metrics
a cell reports are the entries of ``BENCHMARK.json`` that name it; a
per-layer metric ``<m>`` is read by ``bench/metrics/<m>.py``.  Adding a
cell, a configuration, a driver or a metric adds files and entries; no
file here changes.

Set-up (data and weights from the seed, compile, warm-up) is timed from
process start to the first timed call.  The window then runs the
driver's unit of work until ``--seconds`` have passed and ends when the
device has finished it.  With ``--trace 1`` the window is the first
``trace_units`` units of the cell, run under the profiler, and the
result carries the per-layer metrics instead of the end-to-end ones.
After the window the program's state is freed and the driver compares
what the timed path produced with the plain reference: the compared
numbers and their limits are the last lines on standard error and the
last key of the result.

The run exits non-zero and prints no result when JAX finds no
accelerator, fewer chips than the cell asks for, or a device kind that
``bench/peaks.json`` does not hold.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCH = ROOT / "bench"
#: where a traced run writes its profile (deleted once reduced)
TRACE_DIR = ROOT / ".bench_out" / "trace"


class NoDevice(Exception):
    """The machine lacks the accelerator the cell asks for."""


def load_cell(name: str):
    """``(workload, config)`` dicts of cell ``name``."""
    wl = json.loads((BENCH / "workloads" / f"{name}.json").read_text())
    cfg = json.loads((BENCH / "configs" / f"{wl['config']}.json").read_text())
    return wl, cfg


def cell_metrics(manifest: dict, cell: str, trace: bool) -> list:
    """The ``BENCHMARK.json`` metric entries that cell ``cell`` reports:
    its end-to-end metrics, or with ``trace`` its per-layer ones."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def driver_class(kind: str):
    """``bench/drivers/<kind>.py``'s ``Driver``."""
    return importlib.import_module(f"bench.drivers.{kind}").Driver


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(trace, counts)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int):
    """The first ``chips`` accelerator devices and their peaks row."""
    import jax
    from bench.peaks import peaks
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoDevice("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips; JAX found {len(devs)}")
    try:
        peak = peaks(devs[0].device_kind)
    except KeyError as err:
        raise NoDevice(str(err)) from None
    return devs[:chips], peak


def enable_cache() -> None:
    """JAX's persistent compile cache at the checkout's fixed path (or the
    one ``JAX_COMPILATION_CACHE_DIR`` names), every program cached."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def run_cell(cell: str, wl: dict, cfg: dict, *, seed: int, seconds: float,
             trace: bool, devices, peak: dict, manifest: dict,
             t_start: float) -> dict:
    """Set up, time the window, read the metrics, check the outputs."""
    import jax
    from bench.spans import Spans
    from bench.trace_reduce import reduce_trace

    drv = driver_class(wl["driver"])(cfg, wl, seed)
    drv.setup()
    setup_s = time.perf_counter() - t_start - drv.check_overhead_s

    spans = Spans()
    units = steps = 0
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    t0 = time.perf_counter()
    with spans.span("window"):
        while True:
            units += drv.step(spans)
            steps += 1
            if (steps >= wl["trace_units"] if trace
                    else time.perf_counter() - t0 >= seconds):
                break
        drv.drain(spans)
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)

    metrics, breakdown, dev_extra = {}, None, {}
    if trace:
        red = reduce_trace(str(TRACE_DIR), spans.names)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        counts = drv.counts(units, steps)
        counts.update(peak=peak, window_s=red["window_s"],
                      busy_s=red["busy_s"], host_window_s=window_s)
        for m in cell_metrics(manifest, cell, True):
            v = metric_reader(m["name"])(red, counts)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        ops = sorted(red["op_seconds"].items(), key=lambda kv: -kv[1])
        breakdown = {"device_ops": [[k, v] for k, v in ops[:10]],
                     "idle_gaps": [[k, v] for k, v in red["gaps"][:10]]}
        dev_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
    else:
        values = {"setup_s": setup_s, f"{drv.unit}_per_s": units / window_s}
        for m in cell_metrics(manifest, cell, False):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    drv.release()
    checks = drv.check()
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values()) and drv.failed == 0
    dev = devices[0]
    result = {
        "correct": bool(correct),
        "attempted": int(steps),
        "failed": int(drv.failed),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": int(peak_bytes),
                   **dev_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl, cfg = load_cell(args.workload)
    try:
        import repro  # noqa: F401  (the system under test)
        devices, peak = devices_for(wl["chips"])
    except (ImportError, NoDevice) as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    enable_cache()
    result = run_cell(args.workload, wl, cfg, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, peak=peak, manifest=manifest,
                      t_start=_T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
