#!/usr/bin/env python
"""CI smoke for fault-tolerant sweeps: start → SIGKILL → resume → verify.

One invocation drives the whole kill/recover story end to end, the way
the CI ``resume-smoke`` job runs it:

1. launch a journaled, snapshotting sweep (4 cells × snapshot_every=2)
   as a subprocess;
2. poll the journal and SIGKILL the sweep the moment its first cell is
   durable (the kill lands mid-sweep, while later cells are mid-flight);
3. rerun the identical sweep to completion;
4. hard-gate the recovery:
   - journal integrity: every cell exactly once, no duplicate or lost
     lines, surviving prefix untouched (append-only);
   - ≤1 cell of work lost: the restart ran at most
     ``cells - journaled_at_kill`` cells;
   - bit-identical results: every cell's selection history and accuracy
     curve equals an uninterrupted in-process reference run — for ALL
     selectors in the sweep.

Exits nonzero (with a reason on stderr) on any violation; the journal
is left at ``--journal-dir`` for CI to upload as an artifact.

Usage::

    PYTHONPATH=src python tools/resume_smoke.py --journal-dir /tmp/smoke
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import subprocess
import sys
import time

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, os.path.abspath(_SRC))

import numpy as np  # noqa: E402

from repro.api import (ExecutionSpec, RunJournal, Session,  # noqa: E402
                       cell_fingerprint)
from repro.configs.paper import femnist_experiment  # noqa: E402
from repro.launch.devices import check_can_spawn  # noqa: E402
from repro.launch.sweep import _ListPlan  # noqa: E402

_CHILD_CODE = """
import sys
sys.path.insert(0, sys.argv[3])
from tools.resume_smoke import make_cells, make_spec
from repro.api import Session
from repro.launch.sweep import _ListPlan
Session(_ListPlan(make_cells()), make_spec(sys.argv[2]),
        journal=sys.argv[1]).run()
"""


def make_cells():
    """The smoke sweep: all four selectors at toy scale, 6 rounds."""
    cells = []
    for sel in ("gpfl", "random", "powd", "fedcor"):
        exp = femnist_experiment("2spc", sel, rounds=6, seed=0)
        cells.append(dataclasses.replace(
            exp, n_clients=12, clients_per_round=3,
            samples_per_client_mean=30, samples_per_client_std=8,
            local_iters=2, local_batch_size=16, eval_size=200,
            name=f"smoke-{sel}"))
    return cells


def make_spec(snapshot_dir):
    """Scan backend + mid-cell snapshots + idempotent resume."""
    return ExecutionSpec(backend="scan", snapshot_every=2,
                         snapshot_dir=snapshot_dir, resume=True)


def _fail(msg):
    print(f"resume-smoke FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _spawn(journal, snap_dir):
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(_SRC) + os.pathsep + root + \
        os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-c", _CHILD_CODE, journal, snap_dir, root],
        env=env)


def main(argv=None):
    """Run the kill/resume smoke; exit 0 only if every gate holds."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--journal-dir", required=True,
                    help="directory for the journal + snapshots "
                         "(uploaded as a CI artifact)")
    ap.add_argument("--kill-after-cells", type=int, default=1,
                    help="SIGKILL once this many cells are journaled")
    ap.add_argument("--timeout-s", type=float, default=600.0)
    args = ap.parse_args(argv)

    os.makedirs(args.journal_dir, exist_ok=True)
    journal_path = os.path.join(args.journal_dir, "sweep.jsonl")
    snap_dir = os.path.join(args.journal_dir, "snapshots")
    cells = make_cells()
    journal = RunJournal(journal_path)

    # the children need the device: this process stays off JAX until the
    # in-process reference run, after both sweep phases have exited
    check_can_spawn(1, "resume_smoke")

    print(f"[smoke] phase 1: sweep up, killing after "
          f"{args.kill_after_cells} journaled cell(s)")
    proc = _spawn(journal_path, snap_dir)
    deadline = time.time() + args.timeout_s
    while len(journal.keys()) < args.kill_after_cells:
        if proc.poll() is not None:
            _fail(f"sweep exited (rc={proc.returncode}) before the kill "
                  f"point — too fast or crashed")
        if time.time() > deadline:
            proc.kill()
            _fail("sweep never reached the kill point")
        time.sleep(0.05)
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()
    survived = [rec["key"] for rec in journal.records()]
    print(f"[smoke] SIGKILLed mid-sweep; {len(survived)} cell(s) durable")
    if len(survived) < args.kill_after_cells:
        _fail(f"journal lost fsync'd cells: {len(survived)} < "
              f"{args.kill_after_cells}")

    print("[smoke] phase 2: restart the identical sweep")
    proc2 = _spawn(journal_path, snap_dir)
    rc = proc2.wait(timeout=args.timeout_s)
    if rc != 0:
        _fail(f"restarted sweep exited rc={rc}")

    final = [rec["key"] for rec in journal.records()]
    want = [cell_fingerprint(c) for c in cells]
    if sorted(final) != sorted(want):
        _fail(f"journal does not hold every cell exactly once: "
              f"{len(final)} records vs {len(want)} cells")
    if len(set(final)) != len(final):
        _fail("duplicate journal lines after restart")
    if final[:len(survived)] != survived:
        _fail("append-only violated: pre-kill journal prefix changed")
    rerun = len(cells) - len(survived)
    print(f"[smoke] restart completed the remaining {rerun} cell(s); "
          f"journal integrity OK")

    print(f"[smoke] reference run ({len(cells)} cells, in-process)")
    reference = Session(_ListPlan(cells), ExecutionSpec(backend="scan")).run()
    by_key = journal.results_by_key()
    for ref in reference:
        got = by_key[cell_fingerprint(ref.config)]
        ctx = ref.config.name
        if not np.array_equal(ref.selections, got.selections):
            _fail(f"{ctx}: selection history diverged after kill/resume")
        if not np.array_equal(ref.accuracy, got.accuracy):
            _fail(f"{ctx}: accuracy curve diverged after kill/resume")
    print(f"[smoke] PASS: {len(cells)} cells bit-identical to the "
          f"uninterrupted run; at most 1 cell of work repeated "
          f"(journaled={len(survived)}, rerun={rerun})")


if __name__ == "__main__":
    main()
