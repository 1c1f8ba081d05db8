import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent without TPUs.

For every (architecture × input shape × mesh) this lowers + compiles the real
step function — GPFL-gated train_step for train shapes, prefill for
prefill_32k, serve_step (1 token vs a seq_len KV cache) for decode shapes —
against ShapeDtypeStruct inputs (no allocation), then records:

  * memory_analysis()  — bytes/device: proves it fits
  * cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * the collective schedule parsed from the partitioned HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute, with operand bytes)

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
  python -m repro.launch.dryrun --all --json results/dryrun.json
  python -m repro.launch.dryrun --all --multi-pod
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, supports_shape
from repro.dist import (
    init_train_state,
    make_gpfl_train_step,
    make_plain_train_step,
    make_prefill_step,
    make_serve_step,
    rules_for,
)
from repro.launch import mesh as mesh_lib
from repro.models import build, input_specs
from repro.models.common import logical_spec

# `%op.N = <type>[dims]{layout} all-gather(...)` — the partitioned HLO prints
# operands in short form (no types), so we take the RESULT shape of each
# collective as its byte count.  result == operand bytes for all-reduce /
# all-to-all / collective-permute; for all-gather the result is the full
# gathered buffer (== bytes received per device) and for reduce-scatter we
# scale the result back up by the shard count parsed from replica_groups.
COLLECTIVE_RE = re.compile(
    r"=\s+(?:\()?(f64|f32|bf16|f16|s64|s32|s16|s8|u64|u32|u16|u8|pred)"
    r"\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1}


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective bytes from the partitioned HLO."""
    per_kind: dict = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if m is None:
            continue
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        bytes_ = n * DTYPE_BYTES[dt]
        if kind == "reduce-scatter":
            g = GROUPS_RE.search(line)
            if g:
                bytes_ *= len(g.group(1).split(","))
        rec = per_kind.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += bytes_
    per_kind["total_bytes"] = sum(
        v["bytes"] for k, v in per_kind.items() if isinstance(v, dict))
    return per_kind


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def build_lowerable(arch_name: str, shape_name: str, *, multi_pod: bool,
                    mesh=None, step_impl: str = "jvp", remat: str = "full",
                    cfg_override=None, unroll: bool = False,
                    ce_chunks: int = 0, resid_gather: bool = False):
    """Returns (mesh, fn, args, in_shardings, donate) ready for jit().lower().

    cfg_override/unroll back the roofline cost probes: XLA HloCostAnalysis
    counts while-loop bodies once, so probes compile 1- and 2-period UNROLLED
    variants and extrapolate linearly in layer count."""
    cfg = cfg_override if cfg_override is not None else get_arch(arch_name)
    shape = get_shape(shape_name)
    if not supports_shape(cfg, shape):
        raise ValueError(f"{arch_name} skips {shape_name} (DESIGN.md table)")
    mesh = mesh or mesh_lib.make_production_mesh(multi_pod=multi_pod)
    axis = dict(zip(mesh.axis_names, mesh.shape.values())) \
        if hasattr(mesh.shape, "values") else None
    model_size = mesh.shape["model"]
    data_size = mesh.shape["data"]
    rules = rules_for(cfg, shape, model_size=model_size, data_size=data_size,
                      multi_pod=multi_pod)
    if resid_gather:
        rules["_resid_gather"] = True
    api = build(cfg)
    pdt = jnp.bfloat16
    params_abs = api.abstract_params(pdt)
    pspecs = api.param_specs(rules)
    batch_abs = input_specs(cfg, shape)

    bspec = {
        "tokens": logical_spec(("batch", "seq"), rules),
        "labels": logical_spec(("batch", "seq"), rules),
        "patches": logical_spec(("batch", "patches", "embed"), rules),
        "frames": logical_spec(("batch", "frames", "embed"), rules),
    }
    bspec = {k: v for k, v in bspec.items() if k in batch_abs}

    if shape.kind == "train":
        n_groups = data_size * (2 if multi_pod else 1)
        if shape.global_batch % n_groups:
            n_groups = 1
        if step_impl == "plain":
            step = make_plain_train_step(api, lr=1e-3, rules=rules,
                                         remat=remat, grad_specs=pspecs,
                                         unroll=unroll)
        else:
            step = make_gpfl_train_step(
                api, n_groups=n_groups, k_select=max(1, n_groups * 3 // 4),
                total_rounds=10_000, lr=1e-3, rules=rules, remat=remat,
                impl=step_impl, grad_specs=pspecs, unroll=unroll,
                ce_chunks=ce_chunks)
        state_abs = jax.eval_shape(
            lambda p: init_train_state(p, n_groups), params_abs)
        f32specs = jax.tree.map(lambda s: s, pspecs)  # momentum mirrors params
        state_spec = type(state_abs)(
            params=pspecs,
            momentum=f32specs,
            bandit=jax.tree.map(lambda _: P(), state_abs.bandit),
            step=P(),
            prev_loss=P(),
        )
        args = (state_abs, batch_abs)
        shardings = (_named(mesh, state_spec), _named(mesh, bspec))
        return mesh, step, args, shardings, 0  # donate the train state

    if shape.kind == "prefill":
        step = make_prefill_step(api, rules=rules, remat=remat,
                                 unroll=unroll)
        args = (params_abs, batch_abs)
        shardings = (_named(mesh, pspecs), _named(mesh, bspec))
        return mesh, step, args, shardings, None

    # decode
    step = make_serve_step(api, rules=rules, unroll=unroll)
    cache_abs = api.init_cache(shape.global_batch, shape.seq_len,
                               dtype=jnp.bfloat16, abstract=True)
    cspecs = api.cache_specs(rules)
    dec = input_specs(cfg, shape)
    tok_spec = logical_spec(("cache_batch", None), rules)
    args = (params_abs, cache_abs, dec["tokens"], dec["pos"])
    shardings = (_named(mesh, pspecs), _named(mesh, cspecs),
                 NamedSharding(mesh, tok_spec), NamedSharding(mesh, P()))
    return mesh, step, args, shardings, 1  # donate the KV cache


def run_one(arch_name: str, shape_name: str, *, multi_pod: bool = False,
            step_impl: str = "jvp", remat: str = "full",
            verbose: bool = True) -> dict:
    t0 = time.time()
    mesh, fn, args, shardings, donate = build_lowerable(
        arch_name, shape_name, multi_pod=multi_pod, step_impl=step_impl,
        remat=remat)
    donate_kw = {} if donate is None else {"donate_argnums": donate}
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=shardings,
                          **donate_kw).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    mem = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes")}
    ca = compiled.cost_analysis()
    cost = {k: float(ca[k]) for k in (
        "flops", "bytes accessed", "transcendentals",
        "utilization operand", "optimal_seconds") if k in ca}

    colls = parse_collectives(compiled.as_text())

    rec = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step_impl": step_impl,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem,
        "cost": cost,
        "collectives": colls,
    }
    if verbose:
        print(json.dumps(rec, indent=2))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--step-impl", default="jvp",
                    choices=["jvp", "grads", "plain"])
    ap.add_argument("--remat", default="full",
                    choices=["full", "dots", "none"])
    ap.add_argument("--json", default=None, help="append results to file")
    args = ap.parse_args(argv)

    pairs = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                if supports_shape(ARCHS[a], SHAPES[s]):
                    pairs.append((a, s))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch/--shape or --all required")
        pairs = [(args.arch, args.shape)]

    results = []
    failures = 0
    for a, s in pairs:
        print(f"=== dry-run {a} × {s} "
              f"({'2x16x16' if args.multi_pod else '16x16'}) ===",
              flush=True)
        try:
            rec = run_one(a, s, multi_pod=args.multi_pod,
                          step_impl=args.step_impl, remat=args.remat,
                          verbose=not args.json)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if args.multi_pod else "16x16",
                   "status": "fail", "error": str(e)}
            failures += 1
        results.append(rec)
        if args.json:
            with open(args.json, "a") as f:
                f.write(json.dumps(rec) + "\n")
        st = rec["status"]
        print(f"--- {a} × {s}: {st}", flush=True)

    print(f"\n{len(results) - failures}/{len(results)} combinations "
          f"lower+compile OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
