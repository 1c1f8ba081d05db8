#!/usr/bin/env python
"""Bring-up check: the FL round engine and the GPFL-gated trainer on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the client-sharded cohort, 4 chips

One chip runs two phases through the entry points a user calls:

* FL: the paper's FEMNIST 2SPC GPFL cell at its published widths (N=100,
  K=5, 20 local iterations of batch 64, the 784-64-30-62 MLP), cut to a
  few rounds, on the compiled scan and on the host-loop oracle.  Their
  selection histories must be equal and every accuracy finite.
* Gated trainer: ``repro.launch.train`` on ``mamba2-370m`` at full width,
  random weights from a seed.  Losses must be finite, the first near
  ln(vocab), and the first equal to the plain step's on the same params
  and batch.

``--four-chips`` runs only the FL engine's client-sharded cohort
(``shard_clients=4``) against ``shard_clients=1``.

The script refuses any platform but TPU.  The last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``; a failed
check exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: The first gated loss may sit this far (relative) from ln(vocab): random
#: init gives near-uniform next-token probabilities.
INIT_LOSS_RTOL = 0.05
#: Gated vs plain first-step loss, relative.  Both are float32 means over
#: the same tokens; they differ only in summation order (per-group means
#: vs one mean) and XLA's fusion of the two programs.
PLAIN_LOSS_RTOL = 1e-4


class SmokeFailure(Exception):
    """A check of this script failed."""


def _finite(xs) -> bool:
    return all(math.isfinite(float(x)) for x in xs)


def _run_cell(exp, spec):
    """One plan cell through ``Plan.execute_with(spec).run()``; a cell the
    Session caught as failed, or a non-finite accuracy, fails the smoke."""
    from repro.api import Plan
    t0 = time.perf_counter()
    runs = Plan(exp).execute_with(spec).run()
    secs = time.perf_counter() - t0
    if runs.failures:
        raise SmokeFailure(f"{spec.backend} cell failed: "
                           f"{[f.error for f in runs.failures]}")
    res = runs[0]
    if not _finite(res.accuracy):
        raise SmokeFailure(f"{spec.backend}: non-finite accuracy "
                           f"{list(res.accuracy)}")
    return res, secs


def _same_selections(a, b, what: str) -> None:
    import numpy as np
    if a.selections.shape != b.selections.shape:
        raise SmokeFailure(f"{what}: selection shapes differ "
                           f"{a.selections.shape} vs {b.selections.shape}")
    bad = np.flatnonzero((a.selections != b.selections).any(axis=1))
    if bad.size:
        r = int(bad[0])
        raise SmokeFailure(
            f"{what}: selection histories differ from round {r}: "
            f"{a.selections[r].tolist()} vs {b.selections[r].tolist()}")


def fl_phase(exp) -> None:
    """Scan engine vs host-loop oracle on one FL cell."""
    import jax
    import jax.numpy as jnp
    from repro.api import ExecutionSpec
    from repro.core import flat
    from repro.fl.client import minibatch_path
    from repro.fl.simulation import _build_data, resolve_gp_impl
    from repro.kernels.interpret import resolve_interpret
    from repro.kernels.ops import gp_projection
    from repro.models import small

    impl = resolve_gp_impl("auto", False)
    cap = _build_data(exp, exp.seed)[0].capacity
    print(f"[fl] {exp.name}: N={exp.n_clients} K={exp.clients_per_round} "
          f"local_iters={exp.local_iters} batch={exp.local_batch_size} "
          f"rounds={exp.rounds} gp_impl={impl} "
          f"minibatch_path={minibatch_path(cap)} (cap={cap}) "
          f"pallas_interpret={resolve_interpret(None)}", flush=True)
    if impl == "kernel":
        spec = flat.make_flat_spec(small.init(jax.random.key(0), exp.model))
        K, dp = exp.clients_per_round, spec.padded_size
        hlo = jax.jit(gp_projection).lower(
            jnp.zeros((K, dp)), jnp.zeros((dp,))).compile().as_text()
        if "tpu_custom_call" not in hlo:
            raise SmokeFailure("gp_projection compiled without its kernel")
        print(f"[fl] gp_projection compiled as a Mosaic kernel at "
              f"K={K} Dp={dp}", flush=True)

    runs = {}
    for backend in ("scan", "python"):
        res, secs = _run_cell(exp, ExecutionSpec(backend=backend))
        runs[backend] = res
        print(f"[fl] {backend}: {secs:.2f}s including compile; accuracy "
              f"{[round(float(a), 4) for a in res.accuracy]}", flush=True)
    _same_selections(runs["scan"], runs["python"], "fl scan vs python")
    print(f"[fl] scan selection history == host oracle "
          f"({runs['scan'].selections.shape[0]} rounds x "
          f"{runs['scan'].selections.shape[1]} clients)", flush=True)


def gated_phase(arch: str, *, reduce: bool = False, batch: int, seq: int,
                n_groups: int, k_select: int, steps: int, remat: str,
                seed: int = 0) -> None:
    """The gated trainer through ``repro.launch.train.main``, then the
    plain step on the same params and first batch."""
    import jax
    from repro.configs import get_arch
    from repro.dist import init_train_state, make_plain_train_step
    from repro.launch import train
    from repro.models import build

    argv = ["--arch", arch, "--batch", str(batch), "--seq", str(seq),
            "--n-groups", str(n_groups), "--k-select", str(k_select),
            "--steps", str(steps), "--remat", remat, "--seed", str(seed),
            "--log-every", "1"] + (["--reduce"] if reduce else [])
    out = train.main(argv)
    losses = out["losses"]
    print(f"[gated] losses {losses}; compile {out['compile_s']:.2f}s; "
          f"warm step {out['warm_s_per_step']}s", flush=True)
    if not _finite(losses):
        raise SmokeFailure(f"gated: non-finite loss in {losses}")
    cfg = get_arch(arch)
    if reduce:
        cfg = cfg.reduced()
    ln_v = math.log(cfg.vocab_size)
    if abs(losses[0] - ln_v) > INIT_LOSS_RTOL * ln_v:
        raise SmokeFailure(f"gated: first loss {losses[0]} is not near "
                           f"ln(vocab)={ln_v:.4f}")

    api = build(cfg)
    state = init_train_state(api.init(jax.random.key(seed)), n_groups)
    first = next(train.data_stream(cfg, n_groups, batch, seq, seed))
    plain = jax.jit(make_plain_train_step(api, lr=3e-3, remat=remat),
                    donate_argnums=0)
    _, metrics = plain(state, first)
    ref = float(metrics["loss"])
    gap = abs(losses[0] - ref)
    print(f"[gated] first loss {losses[0]!r} vs plain step {ref!r} "
          f"(|diff| {gap:.3g}, ln(vocab) {ln_v:.4f})", flush=True)
    if gap > PLAIN_LOSS_RTOL * abs(ref):
        raise SmokeFailure(f"gated: first loss {losses[0]} differs from "
                           f"the plain step's {ref} by {gap:.3g}")


def _gathers(hlo_line: str, elems: int, devices: int) -> bool:
    """Whether one line of HLO text all-gathers ``elems`` float32 values
    from ``devices`` devices.  The replica groups come in the explicit
    (``{{0,1,2,3}}``) or the iota (``[1,4]<=[4]``) form."""
    head, op, _ = hlo_line.partition(" all-gather")
    if not op:
        return False
    sizes = [math.prod(int(d) for d in dims.split(","))
             for dims in re.findall(r"f32\[([\d,]+)\]", head)]
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", hlo_line)
    group = len(m.group(1).split(",")) if m else 0
    m = re.search(r"replica_groups=\[\d+,(\d+)\]<=", hlo_line)
    group = int(m.group(1)) if m else group
    return elems in sizes and group == devices


def sharded_phase(exp, shards: int) -> None:
    """The client-sharded cohort vs the unsharded engine on one cell."""
    from repro.api import ExecutionSpec
    from repro.fl.engine import ScanEngine

    K = exp.clients_per_round
    eng = ScanEngine(exp, param_layout="flat", shard_clients=shards)
    dp = eng.spec.padded_size
    hlo = eng.lower().compile().as_text()
    # each device trains K/shards clients; the per-device program then
    # all-gathers the (K, Dp) cohort from the `shards` devices
    if not any(_gathers(line, K * dp, shards) for line in hlo.splitlines()):
        raise SmokeFailure(f"sharded: no all-gather of the ({K}, {dp}) "
                           f"cohort over {shards} devices")
    print(f"[sharded] each of {shards} devices trains {K // shards} of the "
          f"(K={K}, Dp={dp}) cohort", flush=True)
    del eng

    runs = {}
    for s in (shards, 1):
        spec = ExecutionSpec(backend="scan", param_layout="flat",
                             shard_clients=s)
        res, secs = _run_cell(exp, spec)
        runs[s] = res
        print(f"[sharded] shard_clients={s}: {secs:.2f}s including compile; "
              f"accuracy {[round(float(a), 4) for a in res.accuracy]}",
              flush=True)
    _same_selections(runs[shards], runs[1], "sharded vs unsharded")
    print(f"[sharded] shard_clients={shards} selection history == "
          f"shard_clients=1", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the client-sharded cohort on 4 chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips; JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)

    from repro.configs.paper import femnist_experiment
    from repro.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            exp = dataclasses.replace(
                femnist_experiment("1spc", "gpfl", rounds=5),
                clients_per_round=20)
            sharded_phase(exp, 4)
        else:
            fl_phase(femnist_experiment("2spc", "gpfl", rounds=5))
            gated_phase("mamba2-370m", batch=8, seq=512, n_groups=4,
                        k_select=2, steps=4, remat="full")
    except SmokeFailure as err:
        print(f"chip_smoke: FAIL: {err}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
