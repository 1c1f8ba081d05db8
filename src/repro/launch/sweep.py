"""Multi-process ``Plan`` executor: cells sharded over worker subprocesses.

A single-process :class:`repro.api.Session` already journals finished
cells and resumes mid-training snapshots — but it is still ONE process:
one OOM, one preemption, one segfault and the sweep stalls until someone
restarts it.  This module runs a Plan across W worker subprocesses:

* **round-robin sharding** — cell i goes to worker ``i % W``; shards are
  disjoint by construction so each worker owns a private journal file
  (``worker{w}.jsonl`` under ``journal_dir``) and no cross-process file
  locking is ever needed (the :class:`repro.api.RunJournal` contract is
  single-writer).
* **retry-on-worker-death** — the parent polls its workers; a worker
  that exits nonzero (SIGKILL, OOM, crash) is respawned on the SAME
  shard + journal up to ``max_restarts`` times, and the journal's
  skip-completed logic means the respawn reruns only the cells the dead
  worker had not finished (at most the one in flight).
* **deterministic merge** — when every shard completes, the parent
  stitches the worker journals back into plan order by cell fingerprint
  and returns a normal :class:`repro.api.RunSet`; restart counts land in
  ``journal_dir/executor_stats.json``.

Crash injection (``crash_after_cells=n``): the FIRST attempt of every
worker hard-exits (``os._exit``, no cleanup — a SIGKILL stand-in) right
after journaling its n-th cell; respawns run clean.  This is the chaos
knob ``tests/test_journal_crash.py`` uses to pin the retry path.

Worker CLI (what the parent spawns)::

    python -m repro.launch.sweep --worker --shard W_IDX --workers W \
        --payload payload.json --journal-dir DIR [--crash-after-cells N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional

from repro.api.journal import RunJournal, cell_fingerprint
from repro.api.results import (CellFailure, RunSet, _config_from_dict,
                               _config_to_dict)
from repro.api.session import Session
from repro.api.spec import ExecutionSpec
from repro.fl.faults import FaultConfig
from repro.fl.latency import (AggregationConfig, LatencyModel,
                              ScenarioConfig)
from repro.fl.preselect import PreselectConfig
from repro.fl.robust import RobustConfig
from repro.launch.devices import check_can_spawn
from repro.utils.compile_cache import enable_compile_cache


class _ListPlan:
    """A pre-expanded plan: just the cells, in order (what a worker
    rebuilds from the payload file — no sweep grammar needed)."""

    def __init__(self, cells: List):
        """Wrap an explicit cell list."""
        self._cells = list(cells)

    def cells(self) -> List:
        """The cells, unchanged and in order."""
        return list(self._cells)


def _spec_to_dict(spec: ExecutionSpec) -> dict:
    """JSON-able spec (scenario dataclasses flattened recursively)."""
    return dataclasses.asdict(spec)


def _spec_from_dict(d: dict) -> ExecutionSpec:
    """Rebuild an :class:`ExecutionSpec` from :func:`_spec_to_dict`
    output (re-hydrating dict-ified ``ScenarioConfig`` /
    ``AggregationConfig`` / ``FaultConfig`` / ``RobustConfig`` /
    ``PreselectConfig`` values)."""
    d = dict(d)
    scn = d.get("scenario")
    if isinstance(scn, dict):
        scn = dict(scn)
        scn["latency"] = LatencyModel(**scn["latency"])
        d["scenario"] = ScenarioConfig(**scn)
    agg = d.get("aggregation")
    if isinstance(agg, dict):
        d["aggregation"] = AggregationConfig(**agg)
    flt = d.get("faults")
    if isinstance(flt, dict):
        d["faults"] = FaultConfig(**flt)
    rb = d.get("aggregator")
    if isinstance(rb, dict):
        d["aggregator"] = RobustConfig(**rb)
    pre = d.get("pre_selection")
    if isinstance(pre, dict):
        d["pre_selection"] = PreselectConfig(**pre)
    return ExecutionSpec(**d)


def _worker_journal(journal_dir: str, shard: int) -> str:
    """The shard's private journal path."""
    return os.path.join(journal_dir, f"worker{shard}.jsonl")


def _shard_indices(n_cells: int, shard: int, workers: int) -> List[int]:
    """Round-robin assignment: the plan indices worker ``shard`` owns."""
    return [i for i in range(n_cells) if i % workers == shard]


def run_worker(payload_path: str, journal_dir: str, shard: int,
               workers: int, crash_after_cells: Optional[int] = None) -> None:
    """One worker's whole life: run this shard's cells, journal each.

    Args:
        payload_path: JSON file written by :func:`run_plan_processes`
            (spec dict + every cell's config dict).
        journal_dir: directory holding the per-shard journals.
        shard: this worker's shard index in ``[0, workers)``.
        workers: total worker count (defines the round-robin).
        crash_after_cells: chaos knob — ``os._exit(1)`` right after the
            n-th journal append (counting cells finished by THIS
            process), simulating a kill mid-sweep.
    """
    with open(payload_path) as fh:
        payload = json.load(fh)
    spec = _spec_from_dict(payload["spec"])
    if spec.telemetry != "off" and spec.telemetry_dir:
        # each shard writes a PRIVATE metric sink (same single-writer
        # contract as the journals); the parent merges after the sweep
        spec = dataclasses.replace(
            spec, telemetry_dir=os.path.join(spec.telemetry_dir,
                                             f"worker{shard}"))
    cells = [_config_from_dict(c) for c in payload["cells"]]
    mine = [cells[i] for i in _shard_indices(len(cells), shard, workers)]
    journal = _worker_journal(journal_dir, shard)

    if crash_after_cells is not None:
        budget = {"left": int(crash_after_cells)}
        orig_append = RunJournal.append

        def crashing_append(self, result):
            key = orig_append(self, result)
            budget["left"] -= 1
            if budget["left"] <= 0:
                # SIGKILL stand-in: no cleanup, no flushes, no excepthook
                os._exit(1)
            return key

        RunJournal.append = crashing_append  # this process only

    Session(_ListPlan(mine), spec, journal=journal).run()


def run_plan_processes(plan, spec: ExecutionSpec, *, workers: int,
                       journal_dir: str, max_restarts: int = 2,
                       crash_after_cells: Optional[int] = None,
                       poll_s: float = 0.2) -> RunSet:
    """Execute a Plan across worker subprocesses, restart-safe.

    Args:
        plan: the :class:`repro.api.Plan` (or any object with
            ``.cells()``) to execute.
        spec: the :class:`ExecutionSpec` every worker runs under
            (validated per cell inside each worker's Session).
        workers: number of worker subprocesses (>= 1).
        journal_dir: directory for the payload file, the per-shard
            journals and ``executor_stats.json``.  Reusing a previous
            run's directory resumes it: workers skip journaled cells.
        max_restarts: respawns allowed PER SHARD after abnormal exits
            before the sweep is declared failed.
        crash_after_cells: chaos knob, passed to every worker's FIRST
            attempt only — each first attempt hard-exits after
            journaling this many cells (tests the retry path).
        poll_s: parent poll interval in seconds.

    The parent never runs JAX code itself. On a TPU host it refuses to
    start when it already holds the backend, or when ``workers > 1``
    (see :mod:`repro.launch.devices`).

    Returns:
        A :class:`repro.api.RunSet` in plan order, merged from the
        per-shard journals.

    Raises:
        RuntimeError: a shard kept dying past ``max_restarts``, the
            journals are missing cells after every shard exited cleanly,
            or (TPU host) the workers could not each reach the chips.
    """
    if workers < 1:
        raise ValueError(f"workers must be >= 1; got {workers}")
    check_can_spawn(workers, "run_plan_processes")
    cells = plan.cells()
    os.makedirs(journal_dir, exist_ok=True)
    payload_path = os.path.join(journal_dir, "payload.json")
    with open(payload_path, "w") as fh:
        json.dump({"spec": _spec_to_dict(spec),
                   "cells": [_config_to_dict(c) for c in cells]}, fh)

    def spawn(shard: int, first: bool) -> subprocess.Popen:
        cmd = [sys.executable, "-m", "repro.launch.sweep", "--worker",
               "--shard", str(shard), "--workers", str(workers),
               "--payload", payload_path, "--journal-dir", journal_dir]
        if first and crash_after_cells is not None:
            cmd += ["--crash-after-cells", str(crash_after_cells)]
        return subprocess.Popen(cmd)

    procs: Dict[int, subprocess.Popen] = {
        s: spawn(s, True) for s in range(workers)}
    restarts = {s: 0 for s in range(workers)}
    while procs:
        time.sleep(poll_s)
        for shard, p in list(procs.items()):
            rc = p.poll()
            if rc is None:
                continue
            del procs[shard]
            if rc == 0:
                continue
            if restarts[shard] >= max_restarts:
                for other in procs.values():
                    other.terminate()
                raise RuntimeError(
                    f"sweep shard {shard} died with exit code {rc} after "
                    f"{restarts[shard]} restart(s) — giving up "
                    f"(journal kept at {_worker_journal(journal_dir, shard)})")
            restarts[shard] += 1
            procs[shard] = spawn(shard, False)

    with open(os.path.join(journal_dir, "executor_stats.json"), "w") as fh:
        json.dump({"workers": workers, "cells": len(cells),
                   "restarts": restarts}, fh, indent=2)

    if spec.telemetry != "off" and spec.telemetry_dir:
        from repro.obs.export import merge_sinks
        merge_sinks(
            [os.path.join(spec.telemetry_dir, f"worker{s}",
                          "metrics.jsonl") for s in range(workers)],
            os.path.join(spec.telemetry_dir, "metrics.jsonl"))

    return merge_shard_journals(cells, journal_dir, workers)


def merge_shard_journals(cells: List, journal_dir: str,
                         workers: int) -> RunSet:
    """Stitch the per-shard journals back into plan order.

    Failure-tolerant: a cell whose latest journal outcome is a
    ``status="failed"`` record (a worker Session degraded gracefully)
    becomes a :class:`repro.api.results.CellFailure` on the returned
    set's ``.failures`` instead of aborting the merge — only a cell with
    NO record at all (the sweep genuinely never got to it) raises.

    Args:
        cells: the plan's cells, in plan order.
        journal_dir: directory holding ``worker{w}.jsonl`` journals.
        workers: shard count (which journals to read).

    Returns:
        A :class:`repro.api.RunSet` of the completed cells in plan
        order, failed cells on ``.failures``.

    Raises:
        RuntimeError: some cell appears in no journal (sweep incomplete).
    """
    by_key: Dict[str, object] = {}
    failed_by_key: Dict[str, dict] = {}
    for shard in range(workers):
        journal = RunJournal(_worker_journal(journal_dir, shard))
        by_key.update(journal.results_by_key())
        failed_by_key.update(journal.failures_by_key())
    results, failures = [], []
    for i, cell in enumerate(cells):
        key = cell_fingerprint(cell)
        if key in by_key:
            results.append(by_key[key])
        elif key in failed_by_key:
            failures.append(CellFailure(
                config=cell, error=failed_by_key[key].get("error", "")))
        else:
            raise RuntimeError(
                f"cell {i} ({cell.name!r}, fingerprint {key[:10]}) missing "
                f"from the worker journals in {journal_dir} — sweep "
                f"incomplete")
    return RunSet(results, failures=failures)


def _main(argv: Optional[List[str]] = None) -> None:
    """CLI entry: only the ``--worker`` mode (parents call
    :func:`run_plan_processes` from Python)."""
    ap = argparse.ArgumentParser(prog="repro.launch.sweep")
    ap.add_argument("--worker", action="store_true", required=True)
    ap.add_argument("--shard", type=int, required=True)
    ap.add_argument("--workers", type=int, required=True)
    ap.add_argument("--payload", required=True)
    ap.add_argument("--journal-dir", required=True)
    ap.add_argument("--crash-after-cells", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    run_worker(args.payload, args.journal_dir, args.shard, args.workers,
               crash_after_cells=args.crash_after_cells)


if __name__ == "__main__":
    _main()
