"""Client-side local training engine (paper Eq. 1-2).

The selected cohort trains as ONE compiled computation: ``vmap`` over clients
of a ``lax.scan`` over local MGD iterations.  Each client runs
``local_iters`` steps of heavy-ball SGD (γ momentum, weight decay) on
replacement-sampled local batches.

Returns per client:
  * final local params  w_i^t
  * final momentum      d_i^t   — the "momentum-based gradient" GPFL projects
  * mean local loss (diagnostics / Pow-d probes)

A client's minibatches for the whole round are drawn and built once, before
the local scan, so no row gather runs inside the local-iteration loop (see
``minibatch_rows``).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.paper import FLExperimentConfig, SmallModelConfig
from repro.models import small


# The tallest client table whose minibatch rows are built by the one-hot
# contraction on a TPU.  The contraction's cost grows with the table height
# and the gather's does not; on a v5e the FEMNIST trainer is the faster with
# it at 226 and 512 rows and the slower at 1,024 (PERF.md, C* micro-run).
CONTRACT_MAX_ROWS = 512


def minibatch_path(cap: int, backend: str | None = None) -> str:
    """How a client table of ``cap`` rows is turned into minibatch rows:
    ``"contract"`` (the exact one-hot contraction on the MXU) on a TPU up to
    ``CONTRACT_MAX_ROWS`` rows, else ``"take"`` (one row gather).  Both give
    the same rows bit for bit; ``backend`` defaults to JAX's default."""
    backend = jax.default_backend() if backend is None else backend
    return "contract" if backend == "tpu" and cap <= CONTRACT_MAX_ROWS \
        else "take"


def _bf16_head(v):
    """``v`` (float32) cut to its leading 8 significant bits, a value that
    bfloat16 holds exactly.  Cut by masking the bits: a TPU may keep a
    float32 → bfloat16 → float32 round trip in float32 (excess precision),
    which would leave nothing for the lower parts."""
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def split_bf16(x):
    """``x`` (float32) as three bfloat16 parts ``hi``, ``mid``, ``lo`` with
    ``(hi + mid) + lo == x`` exactly in float32: ``hi`` holds the leading 8
    bits of the 24-bit significand, ``mid`` the next 8 of the remainder and
    ``lo`` the rest, each exact in bfloat16, each difference exact in
    float32.  Exact for zero and for magnitudes from 2**-103 (below, the
    lower parts fall under float32's normal range and may be flushed to
    zero) up to float32's largest."""
    hi = _bf16_head(x)
    r = x - hi
    mid = _bf16_head(r)
    lo = r - mid
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


def contract_rows(x, idx):
    """``x[idx]`` for a float32 ``x`` of shape ``(cap, *feat)`` and int
    ``idx`` of any shape, as a one-hot contraction on the matrix unit.

    Each bf16 part of ``x`` (``split_bf16``) is contracted with the
    ``(idx.size, cap)`` one-hot selection in one single-pass bf16 dot that
    accumulates in float32.  Every output element is one nonzero product a
    part, so each dot returns its part exactly and the float32 sum rebuilds
    ``x[idx]`` bit for bit over ``split_bf16``'s range (a ``-0.0`` comes
    back as ``+0.0``; a non-finite value in the table spoils its column)."""
    cap = x.shape[0]
    flat = x.reshape(cap, -1)
    onehot = (idx.reshape(-1, 1) == jnp.arange(cap, dtype=idx.dtype)
              ).astype(jnp.bfloat16)

    def dot(part):
        return jnp.dot(onehot, part, preferred_element_type=jnp.float32)

    hi, mid, lo = split_bf16(flat)
    rows = (dot(hi) + dot(mid)) + dot(lo)
    return rows.reshape(idx.shape + x.shape[1:])


def select_labels(y, idx):
    """``y[idx]`` for an integer ``y`` of shape ``(cap,)``, as a sum over
    the one-hot selection: one nonzero term a row, so exact for any value.
    A few thousand single-element gathers cost the TPU tens of µs; this
    costs it one pass over ``idx.size × cap`` elements."""
    hit = idx[..., None] == jnp.arange(y.shape[0], dtype=idx.dtype)
    return jnp.sum(jnp.where(hit, y, jnp.zeros((), y.dtype)), axis=-1)


def minibatch_rows(x, y, idx):
    """The minibatches ``(x[idx], y[idx])`` of one client table: ``x`` is
    ``(cap, *feat)``, ``y`` ``(cap,)``, ``idx`` ``(iters, B)``.  The path
    follows ``minibatch_path`` (float32 tables only take the contraction);
    the rows and labels are the same on either."""
    if x.dtype == jnp.float32 and minibatch_path(x.shape[0]) == "contract":
        return contract_rows(x, idx), select_labels(y, idx)
    xb = jnp.take(x, idx.reshape(-1), axis=0).reshape(idx.shape + x.shape[1:])
    return xb, jnp.take(y, idx, axis=0)


def make_cohort_trainer(exp: FLExperimentConfig) -> Callable:
    """Compile once per experiment; reused every round.

    signature: (params, x, y, sizes, rng) -> (w_i, d_i, loss_i) with leading
    cohort dimension on x/y/sizes and on every output.

    Scan-safety contract: the returned function is also traced INSIDE the
    compiled round engine's ``lax.scan`` body (``repro.fl.engine``), where
    the jit wrapper inlines — keep it free of host callbacks and of shapes
    that depend on data values."""
    cfg = exp.model

    def one_client(params0, x, y, size, rng):
        # step i's draw from its own key, as a per-step draw would make it
        rngs = jax.random.split(rng, exp.local_iters)
        idx = jax.vmap(lambda r: jax.random.randint(
            r, (exp.local_batch_size,), 0, jnp.maximum(size, 1)))(rngs)
        with jax.named_scope("fl_minibatch"):
            xb, yb = minibatch_rows(x, y, idx)

        def step(carry, batch):
            params, d = carry
            loss, grads = jax.value_and_grad(small.loss_fn)(params, batch, cfg)

            def upd(p, g, m):
                gf = g + exp.weight_decay * p
                m_new = exp.momentum * m + gf          # Eq. (1)
                return p - exp.lr * m_new, m_new       # Eq. (2)

            out = jax.tree.map(upd, params, grads, d)
            params = jax.tree.map(lambda o: o[0], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
            d = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda t: isinstance(t, tuple))
            return (params, d), loss

        d0 = jax.tree.map(jnp.zeros_like, params0)
        (params, d), losses = jax.lax.scan(step, (params0, d0),
                                           {"x": xb, "y": yb})
        return params, d, jnp.mean(losses)

    cohort = jax.vmap(one_client, in_axes=(None, 0, 0, 0, 0))
    return jax.jit(cohort)


def make_cohort_loss_eval(exp: FLExperimentConfig, batch_cap: int = 256
                          ) -> Callable:
    """Local loss of the *global* params on each client's data (Pow-d probes,
    FedCor's all-client monitoring).  Evaluates up to batch_cap samples.

    The probe always reduces over EXACTLY ``batch_cap`` rows: clients whose
    padded table is shorter are zero-padded up to it (the mask already
    excludes those rows, and summing a fixed-length vector keeps the probe
    loss bit-identical no matter how tall the backing client table is —
    the batched multi-seed engine stacks tables from different seeds to a
    common height, and the per-seed probes must not notice)."""
    cfg = exp.model

    def one_client(params, x, y, size):
        n = x.shape[0]
        if n < batch_cap:
            pad = batch_cap - n
            x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            y = jnp.pad(y, ((0, pad),))
        take = batch_cap
        logits = small.forward(params, x[:take], cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:take, None], axis=-1)[:, 0]
        per = lse - gold
        mask = (jnp.arange(take) < size).astype(jnp.float32)
        return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    return jax.jit(jax.vmap(one_client, in_axes=(None, 0, 0, 0)))
