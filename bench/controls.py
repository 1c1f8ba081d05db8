#!/usr/bin/env python3
"""Readings from which a cell's correctness limits are set.

    python3 bench/controls.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 1,2,3 [--out <file.json>]

For every seed the program is set up as a run sets it up (which drives
it through the steps a run compares) and its numbers are read against
the reference.  For each control seed the same numbers are read of the
control (the reference at bfloat16, the precision below the
configuration's, in the program's place) and of each fault the driver
plants in the reference.  A limit lies above the largest sound reading
and below the smallest control or fault reading that fails it.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402


def readings(cell: str, wl: dict, cfg: dict, seeds, control_seeds) -> dict:
    """``{seed: {"sound": ..., "control": ..., "faults": {...}}}``."""
    out = {}
    for seed in seeds:
        t0 = time.perf_counter()
        drv = R.driver_class(wl["driver"])(cfg, wl, seed)
        drv.setup()
        drv.release()
        rec = {"sound": drv.readings()}
        if seed in control_seeds:
            rec["control"] = drv.control_readings()
            rec["faults"] = drv.fault_readings()
        rec["seconds"] = time.perf_counter() - t0
        out[seed] = rec
        print(json.dumps({"seed": seed, **rec}, default=str), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    wl, cfg = R.load_cell(args.workload)
    try:
        R.devices_for(wl["chips"])
    except R.NoDevice as err:
        print(f"controls: {err}", file=sys.stderr)
        return 2
    R.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    res = readings(args.workload, wl, cfg, seeds + sorted(ctl - set(seeds)),
                   ctl)
    if args.out:
        Path(args.out).write_text(json.dumps(res, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
