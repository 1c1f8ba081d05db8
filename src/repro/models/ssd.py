"""Mamba-2 / SSD block (arXiv:2405.21060), TPU-adapted.

Training/prefill uses the *chunked dual form*: within-chunk quadratic
(attention-like, MXU-friendly matmuls) + inter-chunk linear recurrence over
chunk states (lax.scan).  Decode is the O(1) recurrent update.  A sequential
per-step oracle (``ssd_reference``) backs the correctness tests.

Layout: d_inner = ssm_expand * d_model, heads = d_inner / ssm_head_dim,
single B/C group (ngroups=1), causal depthwise conv width 4 over (x, B, C).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import ParamDef, Schema, shard

CONV_W = 4


def ssd_dims(cfg: ArchConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    n_heads = d_in // cfg.ssm_head_dim
    conv_dim = d_in + 2 * cfg.ssm_state
    return d_in, n_heads, conv_dim


def ssd_schema(cfg: ArchConfig) -> Schema:
    d = cfg.d_model
    d_in, n_heads, conv_dim = ssd_dims(cfg)
    st = cfg.ssm_state
    return {
        # in_proj → [z: d_in, x: d_in, B: st, C: st, dt: n_heads]
        "w_in": ParamDef((d, 2 * d_in + 2 * st + n_heads), ("embed", "lru")),
        "conv_w": ParamDef((CONV_W, conv_dim), (None, "lru"), "small_normal"),
        "conv_b": ParamDef((conv_dim,), ("lru",), "zeros"),
        "a_log": ParamDef((n_heads,), ("ssm_heads",), "ones"),
        "d_skip": ParamDef((n_heads,), ("ssm_heads",), "ones"),
        "dt_bias": ParamDef((n_heads,), ("ssm_heads",), "zeros"),
        "norm": ParamDef((d_in,), ("lru",), "ones"),
        "w_out": ParamDef((d_in, d), ("lru", "embed")),
    }


def _split_proj(cfg, proj):
    d_in, n_heads, _ = ssd_dims(cfg)
    st = cfg.ssm_state
    z = proj[..., :d_in]
    x = proj[..., d_in : 2 * d_in]
    b = proj[..., 2 * d_in : 2 * d_in + st]
    c = proj[..., 2 * d_in + st : 2 * d_in + 2 * st]
    dt = proj[..., 2 * d_in + 2 * st :]
    return z, x, b, c, dt


def _causal_conv(xbc, w, b):
    """Depthwise causal conv, width CONV_W.  xbc: (B, S, C)."""
    pad = jnp.pad(xbc, ((0, 0), (CONV_W - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :]
        for i in range(CONV_W)
    )
    return jax.nn.silu(out + b[None, None, :])


def _gated_rmsnorm(x, z, scale, eps=1e-6):
    x = x * jax.nn.silu(z)
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def ssd_chunked(xh, dt, a_log, bmat, cmat, chunk: int, h0=None):
    """Chunked SSD scan.

    xh: (B, S, H, P) head inputs; dt: (B, S, H) post-softplus step sizes;
    a_log: (H,) → A = -exp(a_log); bmat/cmat: (B, S, N).
    Returns (y: (B, S, H, P), h_final: (B, H, P, N)).
    """
    B, S, H, Pd = xh.shape
    N = bmat.shape[-1]
    nc = S // chunk
    assert nc * chunk == S, (S, chunk)

    A = -jnp.exp(a_log.astype(jnp.float32))                # (H,)
    da = dt.astype(jnp.float32) * A[None, None, :]         # (B, S, H) log-decay
    xdt = xh.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    def r(t, shape):  # reshape into chunks
        return t.reshape((B, nc, chunk) + shape)

    dac = r(da, (H,))
    cum = jnp.cumsum(dac, axis=2)                          # within-chunk cumsum
    xc = r(xdt, (H, Pd))
    bc = r(bmat.astype(jnp.float32), (N,))
    cc = r(cmat.astype(jnp.float32), (N,))

    # within-chunk (quadratic, masked decay kernel).  Mask BEFORE the exp:
    # non-causal seg is large *positive* (cum decreases in k), so exp(seg)
    # overflows to inf there and where(causal, exp(seg), 0) would feed
    # inf·0 = NaN into the backward pass (bites at chunk ≥ 64 with the
    # a_log="ones" init); exp(-inf) = 0 keeps both directions finite.
    with jax.named_scope("ssd_intra_chunk"):
        seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,q,k,H)
        idx = jnp.arange(chunk)
        causal = idx[:, None] >= idx[None, :]
        L = jnp.exp(jnp.where(causal[None, None, :, :, None], seg, -jnp.inf))
        qk = jnp.einsum("bcqn,bckn->bcqk", cc, bc)           # (B,nc,q,k)
        y_intra = jnp.einsum("bcqk,bcqkh,bckhp->bcqhp", qk, L, xc)

        # chunk summary states: S_c = Σ_k exp(cum_end - cum_k) B_k x_kᵀ
        decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)      # (B,nc,chunk,H)
        sstates = jnp.einsum("bckn,bckh,bckhp->bchpn", bc, decay_to_end, xc)

    # inter-chunk recurrence over chunk states
    with jax.named_scope("ssd_inter_chunk"):
        chunk_decay = jnp.exp(cum[:, :, -1, :])              # (B,nc,H)

        def body(h, xs):
            s_c, dec = xs                                    # (B,H,P,N), (B,H)
            h_new = h * dec[:, :, None, None] + s_c
            return h_new, h                        # emit state *before* chunk

        if h0 is None:
            h0 = jnp.zeros((B, H, Pd, N), jnp.float32)
        h_final, h_prev = jax.lax.scan(
            body, h0,
            (sstates.transpose(1, 0, 2, 3, 4),
             chunk_decay.transpose(1, 0, 2)))
        h_prev = h_prev.transpose(1, 0, 2, 3, 4)             # (B,nc,H,P,N)

        y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp",
                             cc, jnp.exp(cum), h_prev)
    y = (y_intra + y_inter).reshape(B, S, H, Pd)
    return y, h_final


def ssd_reference(xh, dt, a_log, bmat, cmat, h0=None):
    """Sequential per-timestep oracle (tests only)."""
    B, S, H, Pd = xh.shape
    N = bmat.shape[-1]
    A = -jnp.exp(a_log.astype(jnp.float32))

    def step(h, t):
        dtt = dt[:, t].astype(jnp.float32)                 # (B,H)
        dec = jnp.exp(dtt * A[None, :])
        upd = jnp.einsum("bn,bh,bhp->bhpn", bmat[:, t].astype(jnp.float32),
                         dtt, xh[:, t].astype(jnp.float32))
        h = h * dec[:, :, None, None] + upd
        y = jnp.einsum("bn,bhpn->bhp", cmat[:, t].astype(jnp.float32), h)
        return h, y

    if h0 is None:
        h0 = jnp.zeros((B, H, Pd, N), jnp.float32)
    h, ys = jax.lax.scan(step, h0, jnp.arange(S))
    return ys.transpose(1, 0, 2, 3), h


def ssd_apply(p, x, cfg: ArchConfig, rules=None):
    """Full-sequence SSD block: x (B, S, d) → (B, S, d)."""
    B, S, d = x.shape
    d_in, H, conv_dim = ssd_dims(cfg)
    proj = jnp.einsum("bsd,dk->bsk", x, p["w_in"].astype(x.dtype))
    z, xi, bmat, cmat, dt = _split_proj(cfg, proj)
    xbc = jnp.concatenate([xi, bmat, cmat], axis=-1)
    xbc = _causal_conv(xbc, p["conv_w"].astype(x.dtype),
                       p["conv_b"].astype(x.dtype))
    xi, bmat, cmat = (xbc[..., :d_in], xbc[..., d_in : d_in + cfg.ssm_state],
                      xbc[..., d_in + cfg.ssm_state :])
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    xh = xi.reshape(B, S, H, cfg.ssm_head_dim)
    xh = shard(xh, ("batch", "seq", "ssm_heads", None), rules)
    chunk = min(cfg.ssm_chunk, S)
    y, _ = ssd_chunked(xh, dt, p["a_log"], bmat, cmat, chunk)
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = y.astype(x.dtype).reshape(B, S, d_in)
    y = _gated_rmsnorm(y, z, p["norm"])
    out = jnp.einsum("bsk,kd->bsd", y, p["w_out"].astype(x.dtype))
    return shard(out, ("batch", "act_seq", "embed"), rules)


# --- decode -----------------------------------------------------------------

def ssd_cache_init(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    d_in, H, conv_dim = ssd_dims(cfg)
    return {
        "h": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                       jnp.float32),
        "conv": jnp.zeros((batch, CONV_W - 1, conv_dim), dtype),
    }


def ssd_cache_spec(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    d_in, H, conv_dim = ssd_dims(cfg)
    return {
        "h": jax.ShapeDtypeStruct((batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                                  jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, CONV_W - 1, conv_dim), dtype),
    }


def ssd_decode(p, x, cache, cfg: ArchConfig, rules=None):
    """One-token recurrent update.  x: (B, 1, d)."""
    B = x.shape[0]
    d_in, H, conv_dim = ssd_dims(cfg)
    proj = jnp.einsum("bsd,dk->bsk", x, p["w_in"].astype(x.dtype))
    z, xi, bmat, cmat, dt = _split_proj(cfg, proj[:, 0])
    xbc = jnp.concatenate([xi, bmat, cmat], axis=-1)       # (B, conv_dim)
    conv_hist = jnp.concatenate([cache["conv"], xbc[:, None, :]], axis=1)
    w = p["conv_w"].astype(x.dtype)
    conv_out = jnp.einsum("bwc,wc->bc", conv_hist, w) \
        + p["conv_b"].astype(x.dtype)
    conv_out = jax.nn.silu(conv_out)
    xi = conv_out[:, :d_in]
    bmat = conv_out[:, d_in : d_in + cfg.ssm_state]
    cmat = conv_out[:, d_in + cfg.ssm_state :]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # (B,H)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    dec = jnp.exp(dt * A[None, :])
    xh = xi.reshape(B, H, cfg.ssm_head_dim).astype(jnp.float32)
    upd = jnp.einsum("bn,bh,bhp->bhpn", bmat.astype(jnp.float32), dt, xh)
    h = cache["h"] * dec[:, :, None, None] + upd
    y = jnp.einsum("bn,bhpn->bhp", cmat.astype(jnp.float32), h)
    y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(B, 1, d_in).astype(x.dtype)
    y = _gated_rmsnorm(y, z[:, None, :], p["norm"])
    out = jnp.einsum("bsk,kd->bsd", y, p["w_out"].astype(x.dtype))
    new_cache = {"h": h, "conv": conv_hist[:, 1:, :]}
    return out, new_cache
