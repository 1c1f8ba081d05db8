"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One function per paper table/figure (Table II, Fig. 4-7) on the synthetic
FEMNIST stand-in (scaled-down rounds — the offline container has no FEMNIST;
see DESIGN.md), micro-benchmarks of the Pallas kernel wrappers (honest
about interpret mode — see ``_kernel_micro``), the ``engine`` bench
comparing the host round loop against the compiled ``lax.scan`` round
engine (rounds/sec), the ``flat`` bench comparing the engine's tree
vs flat parameter layouts (server-round scans + full engine; see
``_flat_micro``), the ``selectors`` bench comparing all four
selectors across {python, scan} × {1, n_devices} with per-row selection
parity flags (see ``_selector_micro``), the ``sweep`` bench
comparing the batched multi-seed vmapped scan against sequential
per-seed dispatches (see ``_sweep_micro``), and the ``resume`` bench
recording the chunked-scan snapshot overhead and the kill → resume
selection parity for all four selectors (see ``_resume_micro``), and the
``async`` bench pinning the buffered event-scan's sync-reduction parity
and its time-to-accuracy vs. sync under stragglers (see
``_async_micro``), and the ``robust`` bench pinning the robustness
layer's clean-path bit-parity (hard CI gate) and recording the
fault-injection × robust-aggregation head-to-head (see
``_robust_micro``), and the ``preselect`` bench pinning tiered
pre-selection's oracle parity (pool >= N bit-identity, hard CI gate)
and recording the large-K streamed scaling rows — rounds/sec and
device-resident table bytes bounded by the pool, not the population
(see ``_preselect_micro``), and the ``obs`` bench pinning the
observability layer's off-mode bit-parity (hard CI gate), the ≤5%
counter overhead budget, the exact-bytes accounting contract and the
GPFL-vs-random accuracy-within-comm-budget table (see ``_obs_micro``).

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks everything
(CI); ``--full`` runs paper-scale rounds; ``--json PATH`` additionally
writes the engine/flat/selector/sweep/kernel results as machine-readable
JSON (CI uploads ``BENCH_engine.json`` / ``BENCH_flat.json`` /
``BENCH_selectors.json`` / ``BENCH_sweep.json`` / ``BENCH_resume.json``
/ ``BENCH_async.json`` as artifacts — the bench trajectory record).  The
§Roofline analysis is a separate entrypoint (``benchmarks.roofline``)
because it must own XLA_FLAGS=...device_count=512 at process start.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def _kernel_micro():
    """Microbench the kernel entry points — honestly.

    Pallas interpret mode is a correctness oracle, not a performance
    path: on CPU/GPU the kernels run under the interpreter and a timing
    of that says nothing about kernel perf (the old bench reported
    740 ms/call for ``gp_projection`` as if it were the kernel).  Every
    row therefore records the resolved ``interpret`` mode and, when
    interpreted, times the jit'd jnp *reference* implementation instead
    (the fastest deployable path on that backend) under
    ``path: "jnp_ref"``; ``path: "pallas"`` only ever appears where the
    kernel compiles for real (TPU).
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.interpret import resolve_interpret

    interp = resolve_interpret(None)
    path = "jnp_ref" if interp else "pallas"
    rows = []
    rng = np.random.default_rng(0)

    def row(name, pallas_fn, ref_fn, elems, iters=5):
        fn = ref_fn if interp else pallas_fn
        jax.block_until_ready(fn())  # warm + compile
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn())
        rows.append({"name": name,
                     "us_per_call": (time.perf_counter() - t0) / iters * 1e6,
                     "elems": elems, "interpret": interp, "path": path})

    K, D = 16, 262_144
    G = jnp.asarray(rng.normal(size=(K, D)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(D,)), jnp.float32)
    gp_ref = jax.jit(ref.gp_projection_ref)
    row("kernel_gp_projection_16x262k",
        lambda: ops.gp_projection(G, d), lambda: gp_ref(G, d), K * D)
    gps_ref = jax.jit(ref.gp_projection_softmax_ref)
    row("kernel_gp_projection_softmax_16x262k",
        lambda: ops.gp_projection_softmax(G, d), lambda: gps_ref(G, d), K * D)
    prev = jnp.asarray(rng.normal(size=D), jnp.float32)
    dirv = jnp.asarray(rng.normal(size=D), jnp.float32)
    fam_ref = jax.jit(lambda w, p, dd: ref.fedavg_momentum_ref(
        w, p, dd, lr=0.01, gamma=0.9))
    row("kernel_fedavg_momentum_16x262k",
        lambda: ops.fedavg_momentum(G, prev, dirv, lr=0.01, gamma=0.9),
        lambda: fam_ref(G, prev, dirv), K * D)
    n = 1_000_000
    p = jnp.asarray(rng.normal(size=n), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    m = jnp.asarray(rng.normal(size=n), jnp.float32)
    mom_ref = jax.jit(lambda pp, gg, mm: ref.momentum_ref(
        pp, gg, mm, lr=0.01, gamma=0.9))
    row("kernel_momentum_1M",
        lambda: ops.fused_momentum(p, g, m, lr=0.01),
        lambda: mom_ref(p, g, m), n)
    B, S, H, hd = 2, 2048, 2, 64
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    vl = jnp.asarray([S, S // 2], jnp.int32)
    da_ref = jax.jit(ref.decode_attention_ref)
    row("kernel_decode_attention_2x2k",
        lambda: ops.decode_attention(q, kk, vv, vl),
        lambda: da_ref(q, kk, vv, vl), B * S * H * hd, iters=3)
    return rows


def _engine_micro(quick: bool = True):
    """Host-loop vs scanned rounds/sec — the compiled round engine claim.

    Two configs:

    * ``dispatch_bound`` — small model / small eval, so the per-round cost
      is dominated by the 5+ host/device crossings of the Python loop;
      this isolates exactly the overhead the scan engine removes (and is
      where the ≥3× rounds/sec gate applies).
    * ``table2_quick`` — the Table II quick config, which is
      compute-bound (the 1000-sample eval dominates), so the engine gain
      there is Amdahl-limited; recorded for honesty alongside.

    Host-loop throughput is steady-state (round 0's compile dropped);
    engine throughput is a warm second run (compile cached in the
    ``ScanEngine``).
    """
    import dataclasses
    from benchmarks.paper_tables import _scale
    from repro.configs.paper import femnist_experiment
    from repro.fl import ScanEngine, run_experiment

    def one(tag, exp):
        res_py = run_experiment(exp, backend="python")
        py_round = float(res_py.round_time_s[1:].mean())
        eng = ScanEngine(exp)
        eng.run()                       # compile + warm
        res_sc = eng.run()              # steady-state
        sc_round = float(res_sc.round_time_s.mean())
        return {
            "name": f"engine_{tag}",
            "rounds": int(exp.rounds),
            "n_clients": int(exp.n_clients),
            "clients_per_round": int(exp.clients_per_round),
            "python_s_per_round": py_round,
            "scan_s_per_round": sc_round,
            "python_rounds_per_s": 1.0 / py_round,
            "scan_rounds_per_s": 1.0 / sc_round,
            "speedup": py_round / sc_round,
            "selections_match": bool(np.array_equal(res_py.selections,
                                                    res_sc.selections)),
        }

    rounds = 24 if quick else 60
    dispatch = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=rounds, n_clients=64,
        clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256)
    table2 = _scale(femnist_experiment("2spc", "gpfl"), rounds)
    return [one("dispatch_bound", dispatch), one("table2_quick", table2)]


def _server_round_scan(hidden, n_clients, k, rounds, bank_size=4, seed=0):
    """Tree-vs-flat throughput of the SERVER round — GPFL's actual per-round
    overhead (selection → FedAvg → Eq. 1-2 direction → Eq. 3 scoring →
    bandit observe), scanned ``rounds`` times on device.

    Local training is the clients' (parallel, off-server) work, so here the
    cohort uploads come from a small pregenerated bank, handed to each
    layout in its native format (stacked pytree resp. (K, Dp) matrix) —
    both layouts consume bit-identical values and their selection histories
    must match.  This is the dispatch-bound regime the flat workspace
    targets: the tree layout walks every pytree leaf per round where the
    flat layout issues a handful of contiguous passes.

    Returns (tree_s_per_round, flat_s_per_round, selections_match, D).
    """
    import jax
    import jax.numpy as jnp
    from repro.configs.paper import SmallModelConfig
    from repro.core import flat as flat_mod
    from repro.core import gp as gp_mod
    from repro.core import gpcb
    from repro.fl.server import (fedavg, server_update_flat,
                                 update_global_direction)
    from repro.models import small

    N, K, T, BANK = n_clients, k, rounds, bank_size
    cfg = SmallModelConfig(name="bench-mlp", kind="mlp", input_shape=(784,),
                           num_classes=62, hidden=hidden)
    params = small.init(jax.random.key(seed), cfg)
    spec = flat_mod.make_flat_spec(params)
    Dp = spec.padded_size
    rng = np.random.default_rng(seed)

    def mkbank():
        m = rng.normal(size=(BANK, K, Dp)).astype(np.float32) * 0.01
        m[..., spec.size:] = 0.0  # padded tail stays zero, as pack() does
        return jnp.asarray(m)

    def to_tree(mat):
        tr = flat_mod.unpack_stacked(spec, mat.reshape(BANK * K, Dp))
        return jax.tree.map(lambda x: x.reshape((BANK, K) + x.shape[1:]), tr)

    bank_mat, dbank_mat = mkbank(), mkbank()
    bank_tree, dbank_tree = to_tree(bank_mat), to_tree(dbank_mat)
    jitter = jnp.asarray(rng.random((T, N)), jnp.float32)
    latest0 = jnp.asarray(rng.normal(size=N), jnp.float32)
    lr, gamma = 0.005, 0.1

    def build(flat):
        def body(carry, xs):
            t, jit_t = xs
            p, d, band, latest = carry
            scores = gpcb.selection_scores(band, latest, jit_t, t, T)
            ids = jnp.argsort(-scores)[:K]
            if flat:
                w_mat = p[None] + bank_mat[t % BANK]
                p2, d2 = server_update_flat(w_mat, p, d, lr=lr, gamma=gamma)
                gp_s = gp_mod.gp_scores_matrix(dbank_mat[t % BANK], d)
            else:
                w_i = jax.tree.map(lambda pp, b: pp[None] + b[t % BANK],
                                   p, bank_tree)
                d_i = jax.tree.map(lambda b: b[t % BANK], dbank_tree)
                p2 = fedavg(w_i)
                d2 = update_global_direction(d, p, p2, lr, gamma)
                gp_s = gp_mod.gp_scores_stacked(d_i, d)
            band2, latest2 = gpcb.observe(band, latest, ids, gp_s, 0.0, 1.0)
            return (p2, d2, band2, latest2), ids.astype(jnp.int32)

        def run(p, d, band, latest):
            return jax.lax.scan(body, (p, d, band, latest),
                                (jnp.arange(T), jitter))

        if flat:
            args = (flat_mod.pack(spec, params),
                    jnp.zeros((Dp,), jnp.float32), gpcb.init_state(N),
                    latest0)
        else:
            args = (params, jax.tree.map(jnp.zeros_like, params),
                    gpcb.init_state(N), latest0)
        return jax.jit(run), args

    def best(fn, args, reps=7):
        _, ids = jax.block_until_ready(fn(*args))  # compile + warm
        b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            b = min(b, (time.perf_counter() - t0) / T)
        return b, np.asarray(ids)

    fn_t, args_t = build(flat=False)
    fn_f, args_f = build(flat=True)
    tree_s, ids_t = best(fn_t, args_t)
    flat_s, ids_f = best(fn_f, args_f)
    return tree_s, flat_s, bool(np.array_equal(ids_t, ids_f)), spec.size


def _flat_micro(quick: bool = True):
    """Tree vs flat ``param_layout`` (the flat-workspace claim).

    Three rows:

    * ``flat_dispatch_bound`` — the server-round scan on a small width
      (the regime where per-round overhead, not client flops, dominates).
      This is where the ≥1.3× gate applies.
    * ``flat_paper_scale`` — the server-round scan at the paper's FEMNIST
      MLP width (64, 30) and its N=100/K=5 cohort.
    * ``flat_full_engine`` — the complete ``ScanEngine`` tree vs flat,
      recorded for honesty: full simulated round time is dominated by the
      cohort's local training (work a real deployment runs client-side in
      parallel), so the layouts are expected to be near parity here; the
      row's ``selections_match`` doubles as an end-to-end parity check.
    """
    import dataclasses
    from repro.configs.paper import femnist_experiment
    from repro.fl import ScanEngine

    rounds = 128 if quick else 256
    rows = []
    for tag, hidden, n, k in (("dispatch_bound", (32, 16), 64, 4),
                              ("paper_scale", (64, 30), 100, 5)):
        tree_s, flat_s, match, d = _server_round_scan(hidden, n, k, rounds)
        rows.append({
            "name": f"flat_{tag}", "kind": "server_round_scan",
            "rounds": rounds, "n_clients": n, "clients_per_round": k,
            "param_count": d,
            "tree_s_per_round": tree_s, "flat_s_per_round": flat_s,
            "tree_rounds_per_s": 1.0 / tree_s,
            "flat_rounds_per_s": 1.0 / flat_s,
            "speedup": tree_s / flat_s, "selections_match": match,
        })

    exp = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=24 if quick else 60,
        n_clients=64, clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256)
    res = {}
    for layout in ("tree", "flat"):
        eng = ScanEngine(exp, param_layout=layout)
        eng.run()                                  # compile + warm
        res[layout] = min((eng.run() for _ in range(3)),
                          key=lambda r: float(r.round_time_s.mean()))
    tree_s = float(res["tree"].round_time_s.mean())
    flat_s = float(res["flat"].round_time_s.mean())
    rows.append({
        "name": "flat_full_engine", "kind": "full_engine",
        "rounds": int(exp.rounds), "n_clients": int(exp.n_clients),
        "clients_per_round": int(exp.clients_per_round),
        "param_count": None,
        "tree_s_per_round": tree_s, "flat_s_per_round": flat_s,
        "tree_rounds_per_s": 1.0 / tree_s, "flat_rounds_per_s": 1.0 / flat_s,
        "speedup": tree_s / flat_s,
        "selections_match": bool(np.array_equal(res["tree"].selections,
                                                res["flat"].selections)),
        "note": "round time dominated by simulated client-side local "
                "training; see the server_round_scan rows for the "
                "server-side (dispatch-bound) contrast",
    })
    return rows


def _selector_micro(quick: bool = True):
    """Selector-comparison bench: all four selectors × {python, scan} ×
    {1, n_devices} on the dispatch-bound config.

    One row per (selector, backend, device count) with rounds/sec and a
    ``selections_match`` parity flag against that selector's python
    host-loop run — the acceptance gate of the selector-agnostic engine
    (every selector's scan history must replay the host loop
    bit-identically; CI fails on any mismatched row).

    Scan rows run the tree layout on 1 device (the parity oracle) and,
    when ≥2 jax devices are visible (CI forces 2 host CPU devices via
    XLA_FLAGS), the flat layout with the cohort sharded over a
    ``("clients",)`` mesh of the largest device count ≤ n_devices that
    divides K.  Python rows carry the reference throughput; their parity
    flag is trivially true.
    """
    import dataclasses
    import jax
    from repro.configs.paper import femnist_experiment
    from repro.fl import ScanEngine, run_experiment

    rounds = 24 if quick else 60
    ndev = jax.device_count()
    base = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=rounds, n_clients=64,
        clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256)

    rows = []
    for sel in ("random", "gpfl", "powd", "fedcor"):
        exp = dataclasses.replace(base, selector=sel,
                                  name=f"bench-{sel}")
        res_py = run_experiment(exp, backend="python")
        py_round = float(res_py.round_time_s[1:].mean())
        rows.append({
            "name": f"selector_{sel}_python_dev1", "selector": sel,
            "backend": "python", "devices": 1, "param_layout": "tree",
            "rounds": rounds, "s_per_round": py_round,
            "rounds_per_s": 1.0 / py_round, "speedup_vs_python": 1.0,
            "selections_match": True,
        })
        scan_cfgs = [(1, "tree")]
        if ndev >= 2:
            shards = min(ndev, exp.clients_per_round)
            while exp.clients_per_round % shards:
                shards -= 1
            if shards >= 2:
                scan_cfgs.append((shards, "flat"))
        for devs, layout in scan_cfgs:
            eng = ScanEngine(exp, param_layout=layout, shard_clients=devs)
            eng.run()                       # compile + warm
            res_sc = eng.run()              # steady-state
            sc_round = float(res_sc.round_time_s.mean())
            rows.append({
                "name": f"selector_{sel}_scan_dev{devs}", "selector": sel,
                "backend": "scan", "devices": devs, "param_layout": layout,
                "rounds": rounds, "s_per_round": sc_round,
                "rounds_per_s": 1.0 / sc_round,
                "speedup_vs_python": py_round / sc_round,
                "selections_match": bool(np.array_equal(
                    res_py.selections, res_sc.selections)),
            })
    return rows


#: driver executed in a FRESH python process per (selector, mode) — the
#: honest sweep cost: in-process back-to-back timing lets the second mode
#: ride the first one's warm jit caches, which is not what a user's sweep
#: pays.  Timing starts after imports (interpreter+jax startup is
#: identical for both modes) and covers everything a sweep actually
#: costs: dataset builds, init phase, trace+compile, dispatch.
_SWEEP_DRIVER = """\
import dataclasses, sys, time
import numpy as np
sel, mode, n_seeds, rounds, out = (sys.argv[1], sys.argv[2],
                                   int(sys.argv[3]), int(sys.argv[4]),
                                   sys.argv[5])
from repro.configs.paper import femnist_experiment
from repro.fl.engine import BatchedSeedEngine, ScanEngine
base = dataclasses.replace(
    femnist_experiment("2spc", sel), rounds=rounds, n_clients=64,
    clients_per_round=4, samples_per_client_mean=40,
    samples_per_client_std=10, local_iters=3, local_batch_size=16,
    eval_size=256, name=f"sweep-{sel}")
cells = [dataclasses.replace(base, seed=s, name=f"sweep-{sel}/seed={s}")
         for s in range(n_seeds)]
t0 = time.perf_counter()
if mode == "seq":
    res = [ScanEngine(c).run() for c in cells]
else:
    res = BatchedSeedEngine(cells).run()
wall = time.perf_counter() - t0
np.savez(out, wall=np.float64(wall),
         **{f"sel{i}": r.selections for i, r in enumerate(res)})
"""


def _sweep_micro(quick: bool = True):
    """Batched multi-seed vmapped scan vs. sequential per-seed engines.

    The ``repro.api.Session`` claim: S runs differing only in seed cost
    ONE trace/compile and one device dispatch (``BatchedSeedEngine``
    vmaps the round-scan — and, for gpfl, the Algorithm 1 init phase —
    over a leading seed axis) where the sequential path pays S of
    everything.  One row per selector on the dispatch-bound config (tiny
    model/eval — per-run overhead, not client flops, dominates); each
    (selector × mode) runs in a fresh subprocess so neither mode rides
    the other's warm jit caches (see ``_SWEEP_DRIVER``).  The ≥1.5×
    target applies to the gpfl row (the paper's method).

    ``selections_match`` requires EVERY seed's batched selection history
    to be bit-identical to its sequential run — CI fails on any
    mismatched row.
    """
    import os
    import subprocess
    import tempfile
    from repro.configs.paper import SELECTORS
    from repro.launch.devices import check_can_spawn

    check_can_spawn(1, "the sweep bench")
    rounds = 24 if quick else 60
    n_seeds = 8
    rows = []
    with tempfile.TemporaryDirectory() as td:
        for sel in SELECTORS:
            walls, sels = {}, {}
            for mode in ("seq", "batched"):
                out = os.path.join(td, f"{sel}_{mode}.npz")
                subprocess.run(
                    [sys.executable, "-c", _SWEEP_DRIVER, sel, mode,
                     str(n_seeds), str(rounds), out],
                    check=True, env=os.environ.copy())
                data = np.load(out)
                walls[mode] = float(data["wall"])
                sels[mode] = [data[f"sel{i}"] for i in range(n_seeds)]
            per_seed = [bool(np.array_equal(a, b))
                        for a, b in zip(sels["seq"], sels["batched"])]
            total_rounds = n_seeds * rounds
            rows.append({
                "name": f"sweep_{sel}", "selector": sel,
                "seeds": n_seeds, "rounds": rounds,
                "config": "dispatch_bound",
                "timing": "fresh-process end-to-end (builds + init + "
                          "compile + dispatch)",
                "seq_wall_s": walls["seq"],
                "batched_wall_s": walls["batched"],
                "seq_rounds_per_s": total_rounds / walls["seq"],
                "batched_rounds_per_s": total_rounds / walls["batched"],
                "speedup": walls["seq"] / walls["batched"],
                "per_seed_match": per_seed,
                "selections_match": all(per_seed),
            })
    return rows


def _resume_micro(quick: bool = True):
    """Snapshot overhead + resume parity of the chunked scan engine.

    The fault-tolerance claim (ISSUE 6): segmenting the single T-round
    scan into ``snapshot_every=50`` chunks — with the carry written to
    disk at every boundary — costs ≤10% rounds/sec on the
    dispatch-bound config, and a run killed at T/2 then resumed from its
    snapshot replays the uninterrupted selection history bit-identically.
    One row per selector; both engines are warmed (compile excluded) so
    the overhead measured is the real steady-state cost: the extra
    per-chunk dispatches, the host device_get and the fsync'd file
    writes.

    ``resume_match``/``chunked_match`` are hard CI gates for all four
    selectors; ``overhead_pct`` is recorded (warning-gated — shared
    runners are noisy; the committed ``BENCH_resume.json`` documents the
    ≤10% measurement).
    """
    import dataclasses
    import os
    import tempfile
    from repro.configs.paper import SELECTORS, femnist_experiment
    from repro.fl.engine import ScanEngine
    from repro.fl.simulation import _build_data

    rounds = 60 if quick else 120
    every = 50
    kill_at = rounds // 2
    rows = []
    with tempfile.TemporaryDirectory() as td:
        data = None
        for sel in SELECTORS:
            exp = femnist_experiment("2spc", sel, rounds=rounds, seed=0)
            # realistic per-round work (client count / local iters in the
            # paper's regime, scaled): the boundary cost — host sync,
            # device_get, fsync'd write — must amortize against real
            # training rounds, not against an empty dispatch
            exp = dataclasses.replace(
                exp, n_clients=50, clients_per_round=8,
                samples_per_client_mean=60, samples_per_client_std=12,
                local_iters=8, local_batch_size=32, eval_size=512)
            if data is None:  # selector never enters the dataset build
                data = _build_data(exp, exp.seed)

            def timed(eng, repeats=2):
                # best-of-N: one warm run compiles, the min of the next N
                # is the steady-state wall (shared runners are noisy)
                eng.run()
                best, res = float("inf"), None
                for _ in range(repeats):
                    t0 = time.time()
                    res = eng.run()
                    best = min(best, time.time() - t0)
                return res, best

            base_eng = ScanEngine(exp, data=data)
            base, base_wall = timed(base_eng)

            path = os.path.join(td, f"{sel}.ckpt")
            snap_eng = ScanEngine(exp, data=data, snapshot_every=every,
                                  snapshot_path=path)
            snap, snap_wall = timed(snap_eng)
            chunked_match = bool(
                np.array_equal(base.selections, snap.selections))

            os.remove(path)
            kill_eng = ScanEngine(exp, data=data, snapshot_every=every,
                                  snapshot_path=path)
            kill_eng._jit = snap_eng._jit        # session-style jit reuse
            kill_eng.run(until_round=kill_at)    # "killed" at T/2
            res_eng = ScanEngine(exp, data=data, snapshot_every=every,
                                 snapshot_path=path)
            res_eng._jit = snap_eng._jit
            resumed = res_eng.run(resume=True)
            resume_match = bool(
                np.array_equal(base.selections, resumed.selections)
                and np.array_equal(base.accuracy, resumed.accuracy))

            base_rps = rounds / base_wall
            snap_rps = rounds / snap_wall
            rows.append({
                "name": f"resume_{sel}", "selector": sel,
                "rounds": rounds, "snapshot_every": every,
                "kill_at": kill_at, "config": "paper_regime_scaled",
                "timing": "warm steady-state (compile excluded; snapshot "
                          "timing includes the fsync'd carry writes)",
                "baseline_wall_s": base_wall,
                "snapshot_wall_s": snap_wall,
                "baseline_rounds_per_s": base_rps,
                "snapshot_rounds_per_s": snap_rps,
                "overhead_pct": (base_rps - snap_rps) / base_rps * 100.0,
                "chunked_match": chunked_match,
                "resume_match": resume_match,
            })
    return rows


def _async_micro(quick: bool = True):
    """Buffered (FedBuff) event-scan vs. the synchronous round-scan.

    Two claims per ISSUE 7, one row kind each:

    * ``kind="parity"`` — the sync-reduction contract: with buffer
      M = K, ``staleness_discount=1.0``, a zero-latency model and
      E = T events, the buffered event-scan replays the synchronous
      scan bit-identically (selections AND accuracy), for all four
      selectors.  ``reduction_match`` is a **hard CI gate**.
    * ``kind="time_to_acc"`` — the reason to buffer: under the straggler
      latency model, simulated time to reach 90% of the sync run's final
      accuracy.  The sync clock is reconstructed host-side from the SAME
      precomputed completion-time stream the engine consumed (round cost
      = min(max cohort completion, deadline)); the buffered clock is the
      engine's own ``sim_time_s`` event clock.  Both runs consume the
      same total number of client updates (E = T·K/M).  Recorded, not
      gated — the committed ``BENCH_async.json`` documents the
      measurement.
    """
    import dataclasses
    from repro.configs.paper import SELECTORS, femnist_experiment
    from repro.fl.engine import ScanEngine
    from repro.fl.latency import (AggregationConfig, LatencyModel,
                                  ScenarioConfig, completion_time_stream,
                                  make_scenario)

    rounds = 16 if quick else 40
    base = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=rounds, n_clients=32,
        clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256)
    k = base.clients_per_round
    zero_lat = ScenarioConfig(kind="full", latency=LatencyModel(
        local_compute_s=0.0, downlink_s=0.0, uplink_s=0.0,
        straggler_scale=0.0))

    rows = []
    for sel in SELECTORS:
        exp = dataclasses.replace(base, selector=sel, name=f"async-{sel}")
        sync = ScanEngine(exp).run()
        buf = ScanEngine(exp, scenario=zero_lat,
                         aggregation=AggregationConfig(
                             kind="buffered", buffer_size=k,
                             staleness_discount=1.0, events=rounds)).run()
        rows.append({
            "name": f"async_parity_{sel}", "kind": "parity",
            "selector": sel, "rounds": rounds, "buffer_size": k,
            "staleness_discount": 1.0,
            "reduction_match": bool(
                np.array_equal(sync.selections, buf.selections)
                and np.array_equal(sync.accuracy, buf.accuracy)),
        })

    scn = make_scenario("stragglers")
    m = k // 2
    for sel in ("gpfl", "random"):
        exp = dataclasses.replace(base, selector=sel,
                                  name=f"async-tta-{sel}")
        sync = ScanEngine(exp, scenario="stragglers").run()
        # the engine's exact lat stream, regenerated host-side: sync
        # round cost = min(max completion over the cohort, deadline)
        srng = np.random.default_rng((exp.seed, scn.seed, 2))
        lat = completion_time_stream(
            dataclasses.replace(scn.latency, n_clients=exp.n_clients),
            srng, rounds)
        cohort_lat = np.max(
            lat[np.arange(rounds)[:, None], np.asarray(sync.selections)],
            axis=1)
        sync_clock = np.cumsum(np.minimum(cohort_lat,
                                          scn.resolved_deadline()))
        buf = ScanEngine(exp, scenario="stragglers",
                         aggregation=AggregationConfig(
                             kind="buffered", buffer_size=m,
                             staleness_discount=0.5)).run()
        target = 0.9 * float(sync.accuracy[-1])

        def first_hit(acc, clock):
            hit = np.nonzero(np.asarray(acc) >= target)[0]
            return float(clock[hit[0]]) if hit.size else None

        t_sync = first_hit(sync.accuracy, sync_clock)
        t_buf = first_hit(buf.accuracy, buf.sim_time_s)
        rows.append({
            "name": f"async_tta_{sel}", "kind": "time_to_acc",
            "selector": sel, "rounds": rounds, "buffer_size": m,
            "staleness_discount": 0.5,
            "events": rounds * k // m, "target_acc": target,
            "sync_final_acc": float(sync.accuracy[-1]),
            "buffered_final_acc": float(buf.accuracy[-1]),
            "sync_total_sim_s": float(sync_clock[-1]),
            "buffered_total_sim_s": float(buf.sim_time_s[-1]),
            "sync_time_to_target_s": t_sync,
            "buffered_time_to_target_s": t_buf,
            "sim_speedup_to_target": (t_sync / t_buf
                                      if t_sync and t_buf else None),
        })
    return rows


def _robust_micro(quick: bool = True):
    """Adversarial faults × robust aggregation (ISSUE 8).

    Three row kinds:

    * ``kind="parity"`` — the clean-path contract: ``faults=None`` +
      ``aggregator="mean"`` (the spec defaults) must be bit-identical
      (selections AND accuracy) to an engine built without the
      robustness knobs, for all four selectors × both param layouts ×
      sync and buffered aggregation.  ``parity_match`` is a **hard CI
      gate** — the robustness layer may not perturb clean runs at all.
    * ``kind="corruption"`` — the headline head-to-head: 20% of clients
      sign-flip their updates (``signflip_scale=10``) and GPFL vs
      random selection is run under each of the four aggregators.  Each
      row records the aggregator's OWN clean-run final accuracy, the
      corrupted final accuracy, the delta, and the adversaries' share of
      selections.  ``mean_degrades`` / ``robust_within_margin`` document
      the acceptance margins (plain mean loses > 5 accuracy points,
      every robust aggregator stays within 5 points of its clean run) —
      meaningful in the committed default-mode ``BENCH_robust.json``;
      ``--quick`` rounds are too few to train and are not gated on.
    * ``kind="screen"`` / ``kind="quarantine"`` — NaN and noise
      adversaries under the non-finite screen stay finite end-to-end
      (``all_finite``), and ``quarantine_after=1`` collapses GPFL's
      adversary selection share versus the unquarantined run.
    """
    import dataclasses
    from repro.configs.paper import SELECTORS, femnist_experiment
    from repro.fl.engine import ScanEngine
    from repro.fl.faults import FaultConfig, adversary_ids
    from repro.fl.latency import AggregationConfig
    from repro.fl.robust import RobustConfig

    rows = []

    # ---- clean-path bit-parity (hard gate) ----
    p_rounds = 8 if quick else 16
    p_base = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=p_rounds, n_clients=32,
        clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256)
    buf = AggregationConfig(kind="buffered", buffer_size=2,
                            staleness_discount=0.5)
    for layout in ("tree", "flat"):
        for sel in SELECTORS:
            exp = dataclasses.replace(p_base, selector=sel,
                                      name=f"robust-parity-{sel}")
            for agg_name, agg_kw in (("sync", {}),
                                     ("buffered",
                                      dict(scenario="stragglers",
                                           aggregation=buf))):
                plain = ScanEngine(exp, param_layout=layout,
                                   **agg_kw).run()
                defaults = ScanEngine(exp, param_layout=layout,
                                      faults=None, aggregator="mean",
                                      **agg_kw).run()
                rows.append({
                    "name": f"robust_parity_{agg_name}_{layout}_{sel}",
                    "kind": "parity", "selector": sel,
                    "param_layout": layout, "aggregation": agg_name,
                    "rounds": p_rounds,
                    "parity_match": bool(
                        np.array_equal(plain.selections,
                                       defaults.selections)
                        and np.array_equal(plain.accuracy,
                                           defaults.accuracy)),
                })

    # ---- signflip corruption head-to-head (recorded margins) ----
    c_rounds = 16 if quick else 40
    last = max(2, c_rounds // 5)
    flt = FaultConfig(mode="signflip", fraction=0.2, signflip_scale=10.0)
    aggs = {
        "mean": RobustConfig("mean"),
        "trimmed_mean": RobustConfig("trimmed_mean", trim_fraction=0.3),
        "median": RobustConfig("median"),
        "norm_clip": RobustConfig("norm_clip", clip_quantile=0.3),
    }

    def c_exp(sel):
        return dataclasses.replace(
            femnist_experiment("2spc", sel), rounds=c_rounds,
            n_clients=32, clients_per_round=10,
            samples_per_client_mean=60, samples_per_client_std=10,
            local_iters=4, local_batch_size=16, eval_size=256,
            name=f"robust-corrupt-{sel}")

    def final(res):
        return float(np.mean(res.accuracy[-last:]))

    bad = adversary_ids(
        np.random.default_rng((c_exp("gpfl").seed, flt.seed, 3)), 32, flt)
    for sel in ("gpfl", "random"):
        exp = c_exp(sel)
        for agg_name, agg in aggs.items():
            clean = final(ScanEngine(exp, aggregator=agg).run())
            run = ScanEngine(exp, faults=flt, aggregator=agg).run()
            corrupt = final(run)
            delta = clean - corrupt
            rows.append({
                "name": f"robust_signflip_{sel}_{agg_name}",
                "kind": "corruption", "selector": sel,
                "aggregator": agg_name, "rounds": c_rounds,
                "fault_fraction": flt.fraction,
                "signflip_scale": flt.signflip_scale,
                "clean_final_acc": clean,
                "corrupt_final_acc": corrupt,
                "acc_delta": delta,
                "adversary_share": float(
                    np.isin(run.selections, bad).mean()),
                "population_share": float(bad.size / exp.n_clients),
                "mean_degrades": (delta > 0.05
                                  if agg_name == "mean" else None),
                "robust_within_margin": (abs(delta) <= 0.05
                                         if agg_name != "mean" else None),
            })

    # ---- non-finite screen + quarantine ----
    for mode in ("nan", "noise"):
        exp = c_exp("gpfl")
        res = ScanEngine(exp, faults=FaultConfig(mode=mode, fraction=0.2),
                         aggregator="trimmed_mean").run()
        rows.append({
            "name": f"robust_screen_{mode}", "kind": "screen",
            "selector": "gpfl", "fault_mode": mode, "rounds": c_rounds,
            "final_acc": final(res),
            "all_finite": bool(np.isfinite(res.accuracy).all()),
        })
    nan_flt = FaultConfig(mode="nan", fraction=0.2, prob=1.0)
    exp = c_exp("gpfl")
    shares = {}
    for tag, q in (("open", 0), ("quarantined", 1)):
        res = ScanEngine(exp, faults=nan_flt,
                         aggregator=RobustConfig(
                             "mean", quarantine_after=q)).run()
        shares[tag] = float(np.isin(res.selections, bad).mean())
    rows.append({
        "name": "robust_quarantine_gpfl", "kind": "quarantine",
        "selector": "gpfl", "fault_mode": "nan", "rounds": c_rounds,
        "adversary_share_open": shares["open"],
        "adversary_share_quarantined": shares["quarantined"],
        "quarantine_reduces_share": shares["quarantined"]
        < shares["open"],
    })
    return rows


def _preselect_micro(quick: bool = True):
    """Tiered pre-selection (ISSUE 9): parity gate + large-K scaling.

    Three row kinds:

    * ``kind="parity"`` — the oracle-parity contract: with
      ``pool_size >= n_clients`` the tier-1 pool is the identity filter,
      so the pooled engine must replay the plain engine BIT-IDENTICALLY
      (selections AND accuracy) for all four selectors × both param
      layouts × sync and buffered aggregation.  ``parity_match`` is a
      **hard CI gate** — 16 rows, all must pass.
    * ``kind="subset"`` — with a small pool the selected cohort stays
      inside the recorded tier-1 pool every round (gpfl/random/fedcor;
      powd's population-wide candidate draw falls back by design) and a
      same-config rerun reproduces pools + selections bit-identically.
    * ``kind="scale"`` — the reason the tier exists: streamed pooled
      runs at K ∈ {10³, 10⁴, 10⁵} clients (pool 10³) where client
      tables stay HOST-resident and only the double-buffered candidate
      slabs ever reach the device.  ``device_table_bytes`` (analytic:
      2 × pool rows — the two in-flight slabs) vs ``full_table_bytes``
      (what the non-streamed engine would device_put) documents the
      bounded-memory claim; rounds/sec is recorded for the throughput
      trajectory.  ``--quick`` drops the 10⁵ row (CI smoke); the
      committed ``BENCH_preselect.json`` carries the full set.
    """
    import dataclasses
    from repro.configs.paper import SELECTORS, femnist_experiment
    from repro.fl.engine import ScanEngine
    from repro.fl.latency import AggregationConfig
    from repro.fl.preselect import PreselectConfig
    from repro.fl.simulation import _build_data

    rows = []

    # ---- oracle parity at pool >= N (hard gate, 16 rows) ----
    p_rounds = 8 if quick else 16
    p_base = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=p_rounds, n_clients=32,
        clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256)
    data = _build_data(p_base, p_base.seed)
    covering = PreselectConfig(pool_size=64)      # >= N ⇒ identity filter
    buf = AggregationConfig(kind="buffered", buffer_size=2,
                            staleness_discount=0.5)
    for layout in ("tree", "flat"):
        for sel in SELECTORS:
            exp = dataclasses.replace(p_base, selector=sel,
                                      name=f"preselect-parity-{sel}")
            for agg_name, agg_kw in (("sync", {}),
                                     ("buffered",
                                      dict(scenario="stragglers",
                                           aggregation=buf))):
                plain = ScanEngine(exp, param_layout=layout, data=data,
                                   **agg_kw).run()
                pooled = ScanEngine(exp, param_layout=layout, data=data,
                                    pre_selection=covering, **agg_kw).run()
                rows.append({
                    "name": f"preselect_parity_{agg_name}_{layout}_{sel}",
                    "kind": "parity", "selector": sel,
                    "param_layout": layout, "aggregation": agg_name,
                    "rounds": p_rounds, "pool_size": 64,
                    "parity_match": bool(
                        np.array_equal(plain.selections, pooled.selections)
                        and np.array_equal(plain.accuracy,
                                           pooled.accuracy)),
                })

    # ---- small-pool subset + determinism ----
    small = PreselectConfig(pool_size=8)
    for sel in ("gpfl", "random", "fedcor"):
        exp = dataclasses.replace(p_base, selector=sel,
                                  name=f"preselect-subset-{sel}")
        res = ScanEngine(exp, data=data, pre_selection=small).run()
        again = ScanEngine(exp, data=data, pre_selection=small).run()
        subset_ok = all(
            set(res.selections[t]) <= set(res.pools[t])
            for t in range(exp.rounds))
        rows.append({
            "name": f"preselect_subset_{sel}", "kind": "subset",
            "selector": sel, "rounds": p_rounds, "pool_size": 8,
            "subset_ok": bool(subset_ok),
            "deterministic": bool(
                np.array_equal(res.pools, again.pools)
                and np.array_equal(res.selections, again.selections)),
        })

    # ---- large-K streamed scaling (bounded device memory) ----
    pool = 1_000
    scale_ns = (1_000, 10_000) if quick else (1_000, 10_000, 100_000)
    s_rounds = 3
    for n in scale_ns:
        exp = dataclasses.replace(
            femnist_experiment("2spc", "random"), rounds=s_rounds,
            n_clients=n, clients_per_round=8, samples_per_client_mean=2,
            samples_per_client_std=0, local_iters=1, local_batch_size=8,
            eval_size=64, name=f"preselect-scale-{n}")
        sdata = _build_data(exp, exp.seed, host_tables=True)
        store = sdata[0]
        pre = PreselectConfig(pool_size=pool, streamed=True)
        t0 = time.perf_counter()
        res = ScanEngine(exp, data=sdata, pre_selection=pre).run()
        wall = time.perf_counter() - t0
        # one client row in the streamed candidate slab: features +
        # labels + size (what _fetch device_puts per pool member)
        cap = int(store.capacity)
        feat = int(np.prod(store.x.shape[2:]))
        row_bytes = (cap * feat * store.x.dtype.itemsize
                     + cap * store.y.dtype.itemsize
                     + store.sizes.dtype.itemsize)
        p_eff = min(pool, n)
        subset_ok = all(
            set(res.selections[t]) <= set(res.pools[t])
            for t in range(s_rounds))
        rows.append({
            "name": f"preselect_scale_{n}", "kind": "scale",
            "selector": "random", "n_clients": n, "pool_size": p_eff,
            "rounds": s_rounds, "streamed": True,
            "wall_s": wall, "rounds_per_s": s_rounds / wall,
            # double-buffered: at most two pool slabs in flight on device
            "device_table_bytes": 2 * p_eff * row_bytes,
            "full_table_bytes": n * row_bytes,
            "device_bytes_over_full": 2 * p_eff / n,
            "subset_ok": bool(subset_ok),
            "all_finite": bool(np.isfinite(res.accuracy).all()),
        })
        del sdata, store, res
    return rows


def _obs_micro(quick: bool = True):
    """Observability layer (ISSUE 10): off-parity gate + counter overhead.

    Four row kinds:

    * ``kind="parity"`` — the off-mode contract: ``telemetry="off"``
      (the spec default) must be bit-identical (selections AND accuracy)
      to ``telemetry="counters"`` for all four selectors × both param
      layouts × sync and buffered aggregation — counters are EXTRA scan
      outs, never a perturbation of the traced round math.
      ``parity_match`` is a **hard CI gate** — 16 rows, all must pass.
    * ``kind="overhead"`` — the cost of always-on counters: warm
      steady-state rounds/sec of the dispatch-bound config, off vs
      counters.  The ≤5% ``overhead_pct`` budget is a hard CI gate.
    * ``kind="bytes"`` — the accounting contract: the engine's
      ``bytes_down``/``bytes_up`` totals equal the hand computation
      participants × padded-Dp × 4 from the analytic cost model.
    * ``kind="comm_budget"`` — the headline: GPFL vs random best
      accuracy within communication-byte budgets
      (``RunSet.accuracy_at_comm_budget`` over measured counters) — the
      accuracy-at-bytes table EXPERIMENTS.md records.
    """
    import dataclasses
    from repro.api import ExecutionSpec, Plan, Session
    from repro.configs.paper import SELECTORS, femnist_experiment
    from repro.fl.engine import ScanEngine
    from repro.fl.latency import AggregationConfig
    from repro.obs.cost import bytes_per_round

    rows = []

    # ---- off-mode bit-parity (hard gate, 16 rows) ----
    p_rounds = 8 if quick else 16
    p_base = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=p_rounds, n_clients=32,
        clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256)
    buf = AggregationConfig(kind="buffered", buffer_size=2,
                            staleness_discount=0.5)
    for layout in ("tree", "flat"):
        for sel in SELECTORS:
            exp = dataclasses.replace(p_base, selector=sel,
                                      name=f"obs-parity-{sel}")
            for agg_name, agg_kw in (("sync", {}),
                                     ("buffered",
                                      dict(scenario="stragglers",
                                           aggregation=buf))):
                off = ScanEngine(exp, param_layout=layout,
                                 telemetry="off", **agg_kw).run()
                cnt = ScanEngine(exp, param_layout=layout,
                                 telemetry="counters", **agg_kw).run()
                rows.append({
                    "name": f"obs_parity_{agg_name}_{layout}_{sel}",
                    "kind": "parity", "selector": sel,
                    "param_layout": layout, "aggregation": agg_name,
                    "rounds": p_rounds,
                    "parity_match": bool(
                        np.array_equal(off.selections, cnt.selections)
                        and np.array_equal(off.accuracy, cnt.accuracy)),
                })

    # ---- counter overhead (≤5% rounds/sec budget) ----
    o_rounds = 24 if quick else 60
    o_exp = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=o_rounds, n_clients=64,
        clients_per_round=4, samples_per_client_mean=40,
        samples_per_client_std=10, local_iters=3, local_batch_size=16,
        eval_size=256, name="obs-overhead")

    def best_wall(telemetry, repeats=3):
        eng = ScanEngine(o_exp, telemetry=telemetry)
        eng.run()                              # compile + warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            eng.run()
            best = min(best, time.perf_counter() - t0)
        return best

    off_wall = best_wall("off")
    cnt_wall = best_wall("counters")
    off_rps, cnt_rps = o_rounds / off_wall, o_rounds / cnt_wall
    rows.append({
        "name": "obs_overhead_counters", "kind": "overhead",
        "rounds": o_rounds, "config": "dispatch_bound",
        "timing": "warm steady-state best-of-3 (compile excluded)",
        "off_wall_s": off_wall, "counters_wall_s": cnt_wall,
        "off_rounds_per_s": off_rps, "counters_rounds_per_s": cnt_rps,
        "overhead_pct": (off_rps - cnt_rps) / off_rps * 100.0,
    })

    # ---- bytes accounting vs the analytic model ----
    b_exp = dataclasses.replace(p_base, name="obs-bytes")
    res = ScanEngine(b_exp, telemetry="counters").run()
    measured = int(res.metrics["bytes_up"].sum()
                   + res.metrics["bytes_down"].sum())
    analytic = int(bytes_per_round(b_exp)) * p_rounds
    rows.append({
        "name": "obs_bytes_accounting", "kind": "bytes",
        "rounds": p_rounds,
        "clients_per_round": int(b_exp.clients_per_round),
        "measured_total_bytes": measured,
        "analytic_total_bytes": analytic,
        "bytes_match": measured == analytic,
    })

    # ---- GPFL vs random accuracy within comm budgets ----
    # The quickstart regime (N=40, K=5 — 12.5% participation, where
    # selection actually matters; at K/N ≈ 1/3 random coverage washes
    # the selector out), shortened in --quick.
    c_rounds = 16 if quick else 40
    c_base = dataclasses.replace(
        femnist_experiment("2spc", "gpfl"), rounds=c_rounds, n_clients=40,
        clients_per_round=5, samples_per_client_mean=60,
        samples_per_client_std=10, local_iters=4, local_batch_size=16,
        eval_size=256, name="obs-comm")
    plan = Plan(c_base).sweep(selector=["gpfl", "random"]).seeds(2)
    rs = Session(plan, ExecutionSpec(backend="scan",
                                     telemetry="counters")).run()
    per_round = bytes_per_round(c_base)
    for frac in (0.25, 0.5, 1.0):
        budget = int(per_round * c_rounds * frac)
        acc = rs.accuracy_at_comm_budget(budget)
        rows.append({
            "name": f"obs_comm_budget_{int(frac * 100)}pct",
            "kind": "comm_budget", "rounds": c_rounds,
            "budget_bytes": budget, "budget_fraction": frac,
            "gpfl_acc": acc["gpfl"], "random_acc": acc["random"],
        })
    return rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny rounds (CI smoke)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale rounds (hours)")
    ap.add_argument("--only", default=None,
                    help="comma-list: table2,fig4,fig5,fig6,fig7,kernels,"
                         "engine,flat,selectors,sweep,resume,async,robust,"
                         "preselect,obs")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write engine/flat/kernel results as JSON "
                         "(e.g. BENCH_engine.json, BENCH_flat.json)")
    args = ap.parse_args(argv)

    from benchmarks import paper_tables as pt
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    rounds = 12 if args.quick else 60
    only = set(args.only.split(",")) if args.only else \
        {"table2", "fig4", "fig5", "fig6", "fig7", "kernels", "engine",
         "flat", "selectors", "sweep", "resume", "async", "robust",
         "preselect", "obs"}
    bench_data = {}

    print("name,us_per_call,derived")
    t_all = time.time()

    # first: its fresh-process children need the device, which this
    # process holds on a TPU once any other bench has run JAX code
    if "sweep" in only:
        sweep_rows = _sweep_micro(quick=args.quick)
        bench_data["sweep"] = sweep_rows
        for r in sweep_rows:
            per_round_us = r["batched_wall_s"] / (r["seeds"] * r["rounds"]) \
                * 1e6
            print(f"{r['name']},{per_round_us:.0f},"
                  f"seq_rps={r['seq_rounds_per_s']:.2f};"
                  f"batched_rps={r['batched_rounds_per_s']:.2f};"
                  f"speedup={r['speedup']:.2f};"
                  f"selections_match={int(r['selections_match'])}",
                  flush=True)

    if "table2" in only:
        for r in pt.table2_accuracy(rounds=rounds, full=args.full):
            name = f"table2_{r['dataset']}_{r['partition']}_{r['selector']}"
            per_round_us = r["seconds"] / max(1, len(r["result"].accuracy)) \
                * 1e6
            print(f"{name},{per_round_us:.0f},"
                  f"acc15={r['acc_15']:.4f};acc50={r['acc_50']:.4f};"
                  f"acc100={r['acc_100']:.4f}", flush=True)

    if "fig4" in only:
        for r in pt.fig4_coverage(rounds=rounds, full=args.full):
            print(f"fig4_coverage_{r['selector']},0,"
                  f"rounds_to_full={r['rounds_to_full_coverage']};"
                  f"final={r['final_coverage']:.2f}", flush=True)

    if "fig5" in only:
        for r in pt.fig5_histogram(rounds=rounds, full=args.full):
            print(f"fig5_hist_{r['selector']},0,"
                  f"mean={r['mean']:.1f};max={r['max']};"
                  f"tail_ratio={r['tail_ratio']:.2f}", flush=True)

    if "fig6" in only:
        for r in pt.fig6_time(rounds=max(10, rounds // 2), full=args.full):
            print(f"fig6_time_{r['selector']},"
                  f"{r['s_per_round'] * 1e6:.0f},"
                  f"total_s={r['total_s']:.1f}", flush=True)

    if "fig7" in only:
        for r in pt.fig7_alpha_ablation(rounds=rounds, full=args.full):
            print(f"fig7_{r['variant']},0,final_acc={r['final_acc']:.4f}",
                  flush=True)

    if "engine" in only:
        engine_rows = _engine_micro(quick=args.quick)
        bench_data["engine"] = engine_rows
        for r in engine_rows:
            print(f"{r['name']},{r['scan_s_per_round'] * 1e6:.0f},"
                  f"python_rps={r['python_rounds_per_s']:.2f};"
                  f"scan_rps={r['scan_rounds_per_s']:.2f};"
                  f"speedup={r['speedup']:.2f};"
                  f"selections_match={int(r['selections_match'])}",
                  flush=True)

    if "flat" in only:
        flat_rows = _flat_micro(quick=args.quick)
        bench_data["flat"] = flat_rows
        for r in flat_rows:
            print(f"{r['name']},{r['flat_s_per_round'] * 1e6:.0f},"
                  f"tree_rps={r['tree_rounds_per_s']:.2f};"
                  f"flat_rps={r['flat_rounds_per_s']:.2f};"
                  f"speedup={r['speedup']:.2f};"
                  f"selections_match={int(r['selections_match'])}",
                  flush=True)

    if "selectors" in only:
        sel_rows = _selector_micro(quick=args.quick)
        bench_data["selectors"] = sel_rows
        for r in sel_rows:
            print(f"{r['name']},{r['s_per_round'] * 1e6:.0f},"
                  f"rps={r['rounds_per_s']:.2f};"
                  f"speedup={r['speedup_vs_python']:.2f};"
                  f"selections_match={int(r['selections_match'])}",
                  flush=True)

    if "resume" in only:
        resume_rows = _resume_micro(quick=args.quick)
        bench_data["resume"] = resume_rows
        for r in resume_rows:
            print(f"{r['name']},{r['snapshot_wall_s'] / r['rounds'] * 1e6:.0f},"
                  f"baseline_rps={r['baseline_rounds_per_s']:.2f};"
                  f"snapshot_rps={r['snapshot_rounds_per_s']:.2f};"
                  f"overhead_pct={r['overhead_pct']:.1f};"
                  f"chunked_match={int(r['chunked_match'])};"
                  f"resume_match={int(r['resume_match'])}",
                  flush=True)

    if "async" in only:
        async_rows = _async_micro(quick=args.quick)
        bench_data["async"] = async_rows
        for r in async_rows:
            if r["kind"] == "parity":
                print(f"{r['name']},0,"
                      f"reduction_match={int(r['reduction_match'])}",
                      flush=True)
            else:
                spd = r["sim_speedup_to_target"]
                print(f"{r['name']},0,"
                      f"sync_sim_s={r['sync_total_sim_s']:.1f};"
                      f"buf_sim_s={r['buffered_total_sim_s']:.1f};"
                      f"tta_speedup="
                      f"{'n/a' if spd is None else f'{spd:.2f}'}",
                      flush=True)

    if "robust" in only:
        robust_rows = _robust_micro(quick=args.quick)
        bench_data["robust"] = robust_rows
        for r in robust_rows:
            if r["kind"] == "parity":
                print(f"{r['name']},0,"
                      f"parity_match={int(r['parity_match'])}",
                      flush=True)
            elif r["kind"] == "corruption":
                print(f"{r['name']},0,"
                      f"clean={r['clean_final_acc']:.4f};"
                      f"corrupt={r['corrupt_final_acc']:.4f};"
                      f"delta={r['acc_delta']:+.4f};"
                      f"adv_share={r['adversary_share']:.3f}",
                      flush=True)
            elif r["kind"] == "screen":
                print(f"{r['name']},0,"
                      f"final={r['final_acc']:.4f};"
                      f"all_finite={int(r['all_finite'])}", flush=True)
            else:
                print(f"{r['name']},0,"
                      f"share_open={r['adversary_share_open']:.3f};"
                      f"share_quarantined="
                      f"{r['adversary_share_quarantined']:.3f}",
                      flush=True)

    if "preselect" in only:
        pre_rows = _preselect_micro(quick=args.quick)
        bench_data["preselect"] = pre_rows
        for r in pre_rows:
            if r["kind"] == "parity":
                print(f"{r['name']},0,"
                      f"parity_match={int(r['parity_match'])}",
                      flush=True)
            elif r["kind"] == "subset":
                print(f"{r['name']},0,"
                      f"subset_ok={int(r['subset_ok'])};"
                      f"deterministic={int(r['deterministic'])}",
                      flush=True)
            else:
                print(f"{r['name']},"
                      f"{r['wall_s'] / r['rounds'] * 1e6:.0f},"
                      f"rps={r['rounds_per_s']:.2f};"
                      f"dev_bytes={r['device_table_bytes']};"
                      f"full_bytes={r['full_table_bytes']};"
                      f"subset_ok={int(r['subset_ok'])}",
                      flush=True)

    if "obs" in only:
        obs_rows = _obs_micro(quick=args.quick)
        bench_data["obs"] = obs_rows
        for r in obs_rows:
            if r["kind"] == "parity":
                print(f"{r['name']},0,"
                      f"parity_match={int(r['parity_match'])}",
                      flush=True)
            elif r["kind"] == "overhead":
                print(f"{r['name']},"
                      f"{r['counters_wall_s'] / r['rounds'] * 1e6:.0f},"
                      f"off_rps={r['off_rounds_per_s']:.2f};"
                      f"counters_rps={r['counters_rounds_per_s']:.2f};"
                      f"overhead_pct={r['overhead_pct']:.1f}",
                      flush=True)
            elif r["kind"] == "bytes":
                print(f"{r['name']},0,"
                      f"measured={r['measured_total_bytes']};"
                      f"analytic={r['analytic_total_bytes']};"
                      f"bytes_match={int(r['bytes_match'])}",
                      flush=True)
            else:
                print(f"{r['name']},0,"
                      f"budget={r['budget_bytes']};"
                      f"gpfl={r['gpfl_acc']:.4f};"
                      f"random={r['random_acc']:.4f}",
                      flush=True)

    if "kernels" in only:
        kernel_rows = _kernel_micro()
        bench_data["kernels"] = kernel_rows
        for r in kernel_rows:
            print(f"{r['name']},{r['us_per_call']:.0f},"
                  f"elems={r['elems']};path={r['path']};"
                  f"interpret={int(r['interpret'])}", flush=True)

    if args.json:
        import jax
        bench_data["meta"] = {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "jax": jax.__version__,
            "mode": "full" if args.full else
                    ("quick" if args.quick else "default"),
            "total_s": round(time.time() - t_all, 1),
        }
        with open(args.json, "w") as f:
            json.dump(bench_data, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)

    print(f"# total {time.time() - t_all:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
