"""Cells cut to a size a CPU test run holds (the configuration's widths
and the run's shape shrunk), for driving the harness without a chip."""
import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def tiny_cell(name: str, **workload):
    """``(workload, config)`` of cell ``name`` at a CPU test's size, with
    the workload's entries ``workload`` replaced."""
    from bench.run import load_cell
    wl, cfg = (copy.deepcopy(x) for x in load_cell(name))
    wl.update(workload)
    if wl["driver"] == "fl_scan":
        cfg["fl"].update(n_clients=10, clients_per_round=2,
                         samples_per_client=20, eval_size=64, rounds=8,
                         local_iters=2, local_batch_size=8)
        wl.pop("selection_rounds", None)
    else:
        cfg.update(d_model=64, n_layer=2, vocab_size=512, state_size=16,
                   head_dim=16, chunk_size=16)
        wl["train"].update(seq=32)
    return wl, cfg


def run_tiny(name: str, seed: int = 2**31 + 11, **workload) -> dict:
    """One run of the harness on the CPU, its look for a chip skipped."""
    import time
    import jax
    from bench.peaks import peaks
    from bench.run import run_cell
    wl, cfg = tiny_cell(name, **workload)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    return run_cell(name, wl, cfg, seed=seed, seconds=0.3, trace=False,
                    devices=jax.devices()[:1], peak=peaks("TPU v5 lite"),
                    manifest=manifest, t_start=time.perf_counter())
