"""End-to-end distributed training driver (Scale B).

Trains any assigned arch (usually a reduced variant on CPU; the full configs
on a real pod) with the GPFL-gated train step: virtual clients = data-parallel
gradient groups fed from heterogeneous synthetic domain streams.

  PYTHONPATH=src python -m repro.launch.train \
      --arch qwen2.5-3b --reduce --steps 200 --batch 16 --seq 128 \
      --n-groups 4 --k-select 2

``--reduce`` swaps in ``cfg.reduced()`` (CPU-sized).  On one chip drop it
for a config whose full width fits (``mamba2-370m`` with ``--remat full``).
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.data.synthetic import lm_token_stream
from repro.dist import init_train_state, make_gpfl_train_step, \
    make_gpfl_apply_step, make_plain_train_step
from repro.models import build
from repro.checkpoint import save_checkpoint
from repro.utils.compile_cache import enable_compile_cache


def data_stream(cfg, n_groups: int, batch: int, seq: int, seed: int = 0):
    """Heterogeneous per-group token streams (each group = one synthetic
    domain → Non-IID gradient sources, the setting GPFL targets)."""
    tokens = lm_token_stream(n_groups, 262_144, cfg.vocab_size, seed=seed)
    rng = np.random.default_rng(seed)
    per = batch // n_groups
    while True:
        out = np.zeros((batch, seq + 1), np.int32)
        for g in range(n_groups):
            for i in range(per):
                ofs = rng.integers(0, tokens.shape[1] - seq - 1)
                out[g * per + i] = tokens[g, ofs : ofs + seq + 1]
        yield {"tokens": jnp.asarray(out[:, :-1]),
               "labels": jnp.asarray(out[:, 1:])}


def main(argv=None) -> dict:
    """Run the driver; return ``losses`` (one float per step),
    ``compile_s`` (lowering + compiling every step function) and
    ``warm_s_per_step`` (steps after the first, ``None`` for one step)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-groups", type=int, default=4)
    ap.add_argument("--k-select", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--gamma", type=float, default=0.9)
    ap.add_argument("--impl", default="jvp", choices=["jvp", "grads"])
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots"],
                    help="rematerialisation of the layer scan in backward; "
                         "'full' keeps a full-width step inside one chip's "
                         "memory")
    ap.add_argument("--score-every", type=int, default=1,
                    help=">1: re-score groups every Nth step, apply the "
                         "cached bandit selection in between (amortized GPFL)")
    ap.add_argument("--no-gate", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduce:
        cfg = cfg.reduced()
    api = build(cfg)
    params = api.init(jax.random.key(args.seed))
    state = init_train_state(params, args.n_groups)
    del params  # donated with the state on the first step
    kw = dict(n_groups=args.n_groups, k_select=args.k_select,
              total_rounds=args.steps, lr=args.lr, gamma=args.gamma,
              remat=args.remat)
    # the state is donated: its buffers become the next state's, so a
    # step holds one copy of params and momentum, not two
    if args.no_gate:
        step = jax.jit(make_plain_train_step(
            api, lr=args.lr, gamma=args.gamma, remat=args.remat),
            donate_argnums=0)
        apply_step = None
    else:
        step = jax.jit(make_gpfl_train_step(api, impl=args.impl, **kw),
                       donate_argnums=0)
        apply_step = jax.jit(make_gpfl_apply_step(api, **kw),
                             donate_argnums=0) \
            if args.score_every > 1 else None

    stream = data_stream(cfg, args.n_groups, args.batch, args.seq, args.seed)
    batch = next(stream)
    t0 = time.perf_counter()
    step = step.lower(state, batch).compile()
    if apply_step is not None:
        apply_step = apply_step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    print(f"compiled in {compile_s:.1f}s", flush=True)

    losses = []
    t0 = time.time()
    for i in range(args.steps):
        if i:
            batch = next(stream)
        if apply_step is not None and i % args.score_every:
            state, metrics = apply_step(state, batch)
        else:
            state, metrics = step(state, batch)
        losses.append(metrics["loss"])
        if i == 0:
            jax.block_until_ready(metrics["loss"])
            t_warm = time.time()
        if (i + 1) % args.log_every == 0:
            sel = np.asarray(metrics.get("selected_mask",
                                         np.zeros(args.n_groups)))
            print(f"step {i+1:5d} loss={float(metrics['loss']):.4f} "
                  f"ce={float(metrics.get('ce', metrics['loss'])):.4f} "
                  f"selected={np.flatnonzero(sel).tolist()} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
    jax.block_until_ready(state)
    t_end = time.time()
    if args.checkpoint:
        save_checkpoint(args.checkpoint,
                        {"params": state.params}, step=args.steps)
        print("checkpoint →", args.checkpoint)
    print(f"done: {args.steps} steps in {t_end-t0:.1f}s")
    return {"losses": [float(v) for v in losses], "compile_s": compile_s,
            "warm_s_per_step": (t_end - t_warm) / (args.steps - 1)
            if args.steps > 1 else None}


if __name__ == "__main__":
    main()
