"""Pallas TPU kernel: GP scores for K clients in one HBM pass.

Computes  dots = G @ g  and  |g|²  simultaneously, tiling the D axis through
VMEM — the direction vector is streamed exactly once, vs K separate vdots
which re-read it K times (GPFL's score step is bandwidth-bound: 2 bytes/param
per client-group at ~10⁸-10¹¹ params; see DESIGN.md §4).

Grid: (D // BLOCK_D,).  Per step the kernel loads a (K, BLOCK_D) tile of
grads + a (BLOCK_D,) tile of the direction, does an MXU matvec, and
accumulates into the (K,) dots output and the (1,) squared-norm output —
both mapped to the same block every step (revisiting accumulation).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret


DEFAULT_BLOCK_D = 2048


def _kernel(g_ref, d_ref, dots_ref, nsq_ref):
    step = pl.program_id(0)
    gtile = g_ref[...].astype(jnp.float32)      # (K, BD)
    dtile = d_ref[...].astype(jnp.float32)      # (1, BD)

    @pl.when(step == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        nsq_ref[...] = jnp.zeros_like(nsq_ref)

    dots_ref[...] += jnp.sum(gtile * dtile, axis=1, keepdims=True)  # (K, 1)
    nsq_ref[...] += jnp.sum(dtile * dtile, axis=1, keepdims=True)   # (1, 1)


def _kernel_softmax(g_ref, d_ref, dots_ref, nsq_ref, sc_ref, rew_ref):
    step = pl.program_id(0)
    gtile = g_ref[...].astype(jnp.float32)      # (K, BD)
    dtile = d_ref[...].astype(jnp.float32)      # (1, BD)

    @pl.when(step == 0)
    def _init():
        dots_ref[...] = jnp.zeros_like(dots_ref)
        nsq_ref[...] = jnp.zeros_like(nsq_ref)

    dots_ref[...] += jnp.sum(gtile * dtile, axis=1, keepdims=True)  # (K, 1)
    nsq_ref[...] += jnp.sum(dtile * dtile, axis=1, keepdims=True)   # (1, 1)

    # epilogue on the final tile: dots/|g|² are complete, so the scores and
    # their Eq. 5 softmax (K values, resident in VMEM) cost no extra HBM pass
    @pl.when(step == pl.num_programs(0) - 1)
    def _epilogue():
        dn = jnp.maximum(jnp.sqrt(nsq_ref[0, 0]), 1e-12)
        s = dots_ref[...] / dn                               # (K, 1)
        sc_ref[...] = s
        e = jnp.exp(s - jnp.max(s))
        rew_ref[...] = e / jnp.sum(e)


def _pad_operands(grads, direction, block_d):
    K, D = grads.shape
    block_d = min(block_d, D)
    pad = (-D) % block_d
    if pad:
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
        direction = jnp.pad(direction, (0, pad))
    return grads, direction.reshape(1, D + pad), block_d, D + pad


def gp_projection_pallas(grads, direction, *, block_d: int = DEFAULT_BLOCK_D,
                         interpret: Optional[bool] = None):
    """grads (K, D), direction (D,) → (K,) GP scores.

    ``interpret=None`` resolves from the active backend (compiled on TPU,
    interpreted elsewhere)."""
    interpret = resolve_interpret(interpret)
    K = grads.shape[0]
    grads, d2, block_d, Dp = _pad_operands(grads, direction, block_d)

    dots, nsq = pl.pallas_call(
        _kernel,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="gp_projection",
    )(grads, d2)
    return dots[:, 0] / jnp.maximum(jnp.sqrt(nsq[0, 0]), 1e-12)


def gp_projection_softmax_pallas(grads, direction, *,
                                 block_d: int = DEFAULT_BLOCK_D,
                                 interpret: Optional[bool] = None):
    """Fused scores + rewards: grads (K, D), direction (D,) →
    ``(scores (K,), c̃ (K,))`` where c̃ is the Eq. 5 softmax of the scores.

    Same single HBM pass as :func:`gp_projection_pallas`; the softmax runs
    as a last-tile epilogue over the (K,) accumulator already in VMEM, so
    the GPCB reward path consumes kernel output directly."""
    interpret = resolve_interpret(interpret)
    K = grads.shape[0]
    grads, d2, block_d, Dp = _pad_operands(grads, direction, block_d)

    _, _, scores, rewards = pl.pallas_call(
        _kernel_softmax,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((K, 1), jnp.float32),
            jax.ShapeDtypeStruct((K, 1), jnp.float32),
        ],
        interpret=interpret,
    )(grads, d2)
    return scores[:, 0], rewards[:, 0]
