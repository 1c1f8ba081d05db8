"""Roofline analysis from the compiled dry-run (§Roofline deliverable).

Terms per (arch × shape) on the single-pod 16×16 mesh, with the peaks of
the target chip (``TARGET_KIND``) from ``repro.launch.mesh.PEAKS``:

    compute    = HLO_FLOPs_per_device / bf16_flops
    memory     = HLO_bytes_per_device / hbm_bytes_per_s
    collective = collective_bytes_per_device / ici_bytes_per_s_per_link

METHODOLOGY — the scan-trip-count correction:  XLA's HloCostAnalysis counts a
``while`` body once, so a 94-layer scanned model reports ~1 layer of FLOPs.
We therefore compile two UNROLLED probe variants (n_layers = 1× and 2× the
layer-pattern period) of the same (arch, shape, mesh, step) and extrapolate
linearly in layer count:

    total(L) = probe1 + (L − period) · (probe2 − probe1) / period

Embedding / lm-head / loss costs live in the intercept; per-layer costs in
the slope.  Collective bytes come from the partitioned HLO text (result
shapes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute; reduce-scatter scaled by group size) with the same
correction.  Residual inaccuracy: in-layer chunked-attention scans are
probed with the same chunk counts as production, so their body-once costs
appear in the slope and scale with L exactly like production.

MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) for train,
              2·N(_active)·D for prefill/decode.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, supports_shape
from repro.launch.mesh import device_peaks

CHIPS = 256  # single-pod 16×16
TARGET_KIND = "TPU v5 lite"  # TPU v5e, as jax reports its device_kind


def active_params(cfg) -> int:
    """Per-token active parameter count (MoE: k of E experts)."""
    from repro.models import build
    total = build(cfg).count_params()
    if not cfg.is_moe:
        return total
    expert = 3 * cfg.d_model * cfg.d_ff  # swiglu expert
    n_moe_layers = cfg.n_layers
    expert_total = n_moe_layers * cfg.n_experts * expert
    dense_part = total - expert_total
    return dense_part + n_moe_layers * cfg.experts_per_token * expert


def model_flops(cfg, shape) -> float:
    """6·N_active·D for training, 2·N_active·D for inference steps."""
    n_act = active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    tokens = shape.global_batch  # decode: one token per sequence
    return 2.0 * n_act * tokens


def probe_costs(arch_name: str, shape_name: str, *, multi_pod=False,
                step_impl="jvp", remat="full", verbose=False, ce_chunks=0,
                resid_gather=False):
    """Compile 1-period and 2-period unrolled probes → loop-corrected
    per-device (flops, bytes, collective_bytes)."""
    import jax
    from repro.launch.dryrun import build_lowerable, parse_collectives

    cfg = get_arch(arch_name)
    period = cfg.pattern_period
    if cfg.is_encoder_decoder:
        period = 1  # whisper probes scale encoder+decoder together

    def one(n_layers):
        if cfg.is_encoder_decoder:
            c = dataclasses.replace(cfg, n_layers=n_layers,
                                    n_encoder_layers=n_layers)
        else:
            c = dataclasses.replace(cfg, n_layers=n_layers)
        mesh, fn, args, sh, don = build_lowerable(
            arch_name, shape_name, multi_pod=multi_pod, step_impl=step_impl,
            remat=remat, cfg_override=c, unroll=True, ce_chunks=ce_chunks,
            resid_gather=resid_gather)
        kw = {} if don is None else {"donate_argnums": don}
        with jax.set_mesh(mesh):
            comp = jax.jit(fn, in_shardings=sh, **kw).lower(*args).compile()
        ca = comp.cost_analysis()
        colls = parse_collectives(comp.as_text())
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)),
                float(colls["total_bytes"]))

    p1 = one(period)
    p2 = one(2 * period)
    L = cfg.n_layers
    out = tuple(a + (L - period) * (b - a) / period for a, b in zip(p1, p2))
    if verbose:
        print(f"  probe {arch_name}/{shape_name}: 1p={p1} 2p={p2} → {out}")
    return {"flops": out[0], "bytes": out[1], "collective_bytes": out[2],
            "probe_1p": p1, "probe_2p": p2}


def roofline_terms(costs: dict, cfg, shape) -> dict:
    peaks = device_peaks(TARGET_KIND)
    compute_s = costs["flops"] / peaks["bf16_flops"]
    memory_s = costs["bytes"] / peaks["hbm_bytes_per_s"]
    coll_s = costs["collective_bytes"] / peaks["ici_bytes_per_s_per_link"]
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dom = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_global = costs["flops"] * CHIPS
    return {
        **terms,
        "dominant": dom.replace("_s", ""),
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "step_seconds_lower_bound": max(terms.values()),
    }


SUGGESTIONS = {
    "compute": "raise arithmetic efficiency: fuse attention (Pallas flash), "
               "drop remat recompute via policy=dots, or grow per-chip batch",
    "memory": "cut HBM traffic: fuse optimizer (Pallas momentum kernel), "
              "bf16 residuals end-to-end, chunked CE to avoid f32 logits",
    "collective": "re-route comms: all-to-all expert dispatch instead of "
                  "ff-sharded weight gathers; overlap via async collectives",
}


def analyze_pair(arch_name: str, shape_name: str, *, step_impl="jvp",
                 remat="full", verbose=False, ce_chunks=0,
                 resid_gather=False) -> dict:
    cfg = get_arch(arch_name)
    shape = get_shape(shape_name)
    costs = probe_costs(arch_name, shape_name, step_impl=step_impl,
                        remat=remat, verbose=verbose, ce_chunks=ce_chunks,
                        resid_gather=resid_gather)
    terms = roofline_terms(costs, cfg, shape)
    terms["suggestion"] = SUGGESTIONS[terms["dominant"]]
    return {"arch": arch_name, "shape": shape_name, "mesh": "16x16",
            "step_impl": step_impl, "remat": remat, "ce_chunks": ce_chunks,
            "resid_gather": resid_gather, **costs, **terms}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--step-impl", default="jvp")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--ce-chunks", type=int, default=0)
    ap.add_argument("--resid-gather", action="store_true",
                    help="force bf16 placement of the seq-parallel gathers")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    pairs = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in sorted(ARCHS) for s in SHAPES
              if supports_shape(ARCHS[a], SHAPES[s])])
    failures = 0
    for a, s in pairs:
        print(f"=== {a} × {s} (impl={args.step_impl}, remat={args.remat}, "
              f"ce_chunks={args.ce_chunks}) ===", flush=True)
        try:
            rec = analyze_pair(a, s, step_impl=args.step_impl,
                               remat=args.remat, verbose=True,
                               ce_chunks=args.ce_chunks,
                               resid_gather=args.resid_gather)
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            rec = {"arch": a, "shape": s, "error": str(e)}
            failures += 1
        print(json.dumps({k: v for k, v in rec.items()
                          if k not in ("probe_1p", "probe_2p")}, indent=1))
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(rec) + "\n")
    return 1 if failures else 0


if __name__ == "__main__":
    import os
    import sys
    assert os.environ.get("XLA_FLAGS"), \
        "run via: XLA_FLAGS=--xla_force_host_platform_device_count=512 " \
        "python -m benchmarks.roofline"
    sys.exit(main())
