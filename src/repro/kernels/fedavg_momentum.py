"""Pallas TPU kernel: the whole server-side update in ONE tiled HBM pass.

Given the cohort's locally-trained parameter matrix W (K, D), the previous
global params w (D,) and the global momentum direction d (D,):

    w'  = Σ_i λ_i W_i                      (weighted FedAvg)
    g   = (w − w') / η                     (effective aggregated descent)
    d'  = γ·d + g                          (Eq. 1-2 momentum-direction)

Unfused this is a leafwise walk over the pytree — mean, sub, scale and
axpy per leaf, each a separate HBM round-trip.  Fused over the flat
workspace it is exactly (K + 2) reads + 2 writes per element: one grid
step loads a (K, BLOCK_D) tile of W plus the matching (BLOCK_D,) tiles
of w and d, reduces over K on the VPU, and writes the new params and
direction tiles.  The cohort weights λ (K,) ride along in full every
step (K is tiny); the learning rate arrives as a (1,) array so η sweeps
don't recompile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.interpret import resolve_interpret

DEFAULT_BLOCK_D = 2048


def _kernel(inv_lr_ref, w_ref, prev_ref, dir_ref, wt_ref, pout_ref, dout_ref,
            *, gamma):
    w = w_ref[...].astype(jnp.float32)          # (K, BD)
    wt = wt_ref[...].astype(jnp.float32)        # (K, 1)
    avg = jnp.sum(w * wt, axis=0, keepdims=True)            # (1, BD)
    # multiply by the host-precomputed 1/η — same algebra as the jnp
    # update_global_direction_flat path, not a per-element divide
    g_eff = (prev_ref[...].astype(jnp.float32) - avg) * inv_lr_ref[0]
    d_new = gamma * dir_ref[...].astype(jnp.float32) + g_eff
    pout_ref[...] = avg.astype(pout_ref.dtype)
    dout_ref[...] = d_new


def fedavg_momentum_pallas(w_matrix, w_prev, direction, weights, *, lr,
                           gamma: float, block_d: int = DEFAULT_BLOCK_D,
                           interpret: Optional[bool] = None):
    """W (K, D), w_prev (D,), direction (D,), weights (K,) summing to 1
    → (new_params (D,), new_direction (D,)).

    ``interpret=None`` resolves from the active backend (compiled on TPU,
    interpreted elsewhere)."""
    interpret = resolve_interpret(interpret)
    K, D = w_matrix.shape
    block_d = min(block_d, D)
    pad = (-D) % block_d
    if pad:
        w_matrix = jnp.pad(w_matrix, ((0, 0), (0, pad)))
        w_prev = jnp.pad(w_prev, (0, pad))
        direction = jnp.pad(direction, (0, pad))
    Dp = D + pad
    if isinstance(lr, (int, float)):
        # python scalar: take the reciprocal host-side, exactly as the jnp
        # server_update_flat path does
        inv_lr = jnp.asarray([1.0 / max(lr, 1e-12)], jnp.float32)
    else:  # traced lr (e.g. a schedule value)
        inv_lr = 1.0 / jnp.maximum(jnp.asarray(lr, jnp.float32).reshape(1),
                                   1e-12)
    wt2 = weights.astype(jnp.float32).reshape(K, 1)

    p_new, d_new = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma),
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((K, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Dp), w_prev.dtype),
            jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        ],
        interpret=interpret,
        name="fedavg_momentum",
    )(inv_lr, w_matrix, w_prev.reshape(1, Dp), direction.reshape(1, Dp), wt2)
    p_new, d_new = p_new[0], d_new[0]
    if pad:
        p_new, d_new = p_new[:D], d_new[:D]
    return p_new, d_new
