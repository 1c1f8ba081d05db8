#!/usr/bin/env python
"""Time the two ways of building a round's local minibatch rows, and check
that they give the same bits.

For each client model of the paper (the FEMNIST MLP, 784 features, and the
CIFAR-10 CNN, 32x32x3 = 3,072) and each client table height ``cap``, the
cohort trainer is built with its rows from the one-hot contraction and from
one row gather (``repro.fl.client.minibatch_path`` forced).  The cohort,
local iterations and batch are the paper's for each model.  A time is one
trainer call's share of ``--calls`` calls run in one compiled loop (each on
keys folded with its step, so none is the same), the best of ``--repeats``.

Before timing, both paths' rows and labels are compared bit for bit with
``x[idx]`` taken on the host, and the two trainers' ``(w_i, d_i, loss_i)``
with each other, on tables whose magnitudes span 1e-6 to 1e6 (with padded
and empty clients).

    PYTHONPATH=src python tools/minibatch_rows_bench.py [--caps 226,512]

Prints one JSON line per (model, cap), then a last line
``{"contract_max_rows": C, ...}``: the tallest measured table up to which
the contraction's trainer is the faster for every model.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, os.path.abspath(_SRC))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper import cifar10_experiment, femnist_experiment  # noqa: E402,E501
from repro.fl import client  # noqa: E402
from repro.models import small  # noqa: E402


@contextlib.contextmanager
def forced(path: str):
    """Trace ``minibatch_rows`` on ``path`` whatever the table height."""
    rule = client.minibatch_path
    client.minibatch_path = lambda cap, backend=None: path
    try:
        yield
    finally:
        client.minibatch_path = rule


def inputs(exp, cap: int, seed: int):
    """A cohort's tables with magnitudes 1e-6..1e6 and signs mixed, sizes
    from full down to empty, and per-client keys."""
    rng = np.random.default_rng(seed)
    K, feat = exp.clients_per_round, exp.model.input_shape
    mag = 10.0 ** rng.uniform(-6, 6, (K, cap) + feat)
    x = (rng.choice([-1.0, 1.0], mag.shape) * mag).astype(np.float32)
    y = rng.integers(0, exp.model.num_classes, (K, cap)).astype(np.int32)
    sizes = np.linspace(cap, 0, K).astype(np.int32)
    keys = jax.random.split(jax.random.key(seed), K)
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(sizes), keys


def rows_fn(exp):
    """The cohort's indices, rows and labels for the whole round, as the
    trainer builds them."""
    def one(x, y, size, key):
        idx = jax.vmap(lambda r: jax.random.randint(
            r, (exp.local_batch_size,), 0, jnp.maximum(size, 1)))(
            jax.random.split(key, exp.local_iters))
        return (idx,) + client.minibatch_rows(x, y, idx)
    return jax.jit(jax.vmap(one))


def looped(trainer, calls: int):
    """``calls`` trainer calls in one compiled loop, each on its own keys."""
    def run(params, x, y, sizes, keys):
        def body(i, acc):
            ks = jax.vmap(lambda k: jax.random.fold_in(k, i))(keys)
            return acc + jnp.sum(trainer(params, x, y, sizes, ks)[2])
        return jax.lax.fori_loop(0, calls, body, jnp.float32(0))
    return jax.jit(run)


def timed(fn, args, calls: int, repeats: int) -> float:
    """Best over ``repeats`` of the seconds a call of ``fn``'s loop."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def same_bits(a, b) -> bool:
    return all(np.shape(u) == np.shape(v)
               and np.asarray(u).tobytes() == np.asarray(v).tobytes()
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def measure(exp, cap: int, calls: int, repeats: int, seed: int) -> dict:
    x, y, sizes, keys = inputs(exp, cap, seed)
    params = small.init(jax.random.key(seed), exp.model)
    out = {"model": exp.model.name, "cap": cap,
           "features": int(np.prod(exp.model.input_shape)),
           "K": exp.clients_per_round, "iters": exp.local_iters,
           "batch": exp.local_batch_size}
    xh, yh = np.asarray(x), np.asarray(y)
    trained = {}
    for path in ("contract", "take"):
        with forced(path):
            idx, xb, yb = rows_fn(exp)(x, y, sizes, keys)
            idx = np.asarray(idx)
            k = np.arange(idx.shape[0])[:, None, None]
            out[f"rows_equal.{path}"] = same_bits(
                (xb, yb), (xh[k, idx], yh[k, idx]))
            trainer = client.make_cohort_trainer(exp)
            trained[path] = trainer(params, x, y, sizes, keys)
            out[f"trainer_us.{path}"] = 1e6 * timed(
                looped(trainer, calls), (params, x, y, sizes, keys),
                calls, repeats)
    out["trainer_equal"] = same_bits(trained["contract"], trained["take"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--caps", default="226,512,1024,2048")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--models", default="femnist,cifar10",
                    help="femnist (the MLP) and/or cifar10 (the CNN)")
    args = ap.parse_args(argv)
    caps = [int(c) for c in args.caps.split(",")]
    make = {"femnist": femnist_experiment, "cifar10": cifar10_experiment}
    exps = [make[m]() for m in args.models.split(",")]
    faster = {}
    ok = True
    for exp in exps:
        for cap in caps:
            row = measure(dataclasses.replace(exp, seed=args.seed), cap,
                          args.calls, args.repeats, args.seed)
            row["device"] = jax.devices()[0].device_kind
            ok &= (row["rows_equal.contract"] and row["rows_equal.take"]
                   and row["trainer_equal"])
            faster.setdefault(cap, []).append(
                row["trainer_us.contract"] < row["trainer_us.take"])
            print(json.dumps(row), flush=True)
    won = [c for c in sorted(faster) if all(faster[c])]
    lost = [c for c in sorted(faster) if not all(faster[c])]
    cmax = max((c for c in won if not any(d < c for d in lost)),
               default=0)
    print(json.dumps({"contract_max_rows": cmax, "all_equal": ok}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
