"""Random Mamba-2 weights from a seed, made on the device in one jitted call.

The layout is the decoder stack's: the 48 SSD layers stacked on a leading
axis under ``stack/pos0``, the embedding tied to the output head.  The
initial values follow Mamba-2's published defaults (the ``mamba_ssm``
``Mamba2`` module): A = −U(1, 16) per head (stored as its log), dt drawn
log-uniformly in [1e-3, 0.1] and stored as softplus⁻¹(dt), D = 1, the
depthwise conv U(±1/√4), projections normal/√fan_in with the out
projection further scaled by 1/√n_layer, the embedding N(0, 0.02), norm
scales 1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

CONV_WIDTH = 4


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    heads = d_in // cfg["head_dim"]
    st = cfg["state_size"] * cfg.get("n_groups", 1)
    return {"d": d, "d_in": d_in, "heads": heads, "st": st,
            "conv": d_in + 2 * st, "proj": 2 * d_in + 2 * st + heads,
            "L": cfg["n_layer"], "V": cfg["vocab_size"]}


def layout(cfg: dict) -> dict:
    """Leaf shapes of the parameter tree."""
    k = dims(cfg)
    L = k["L"]
    return {
        "embed": {"embedding": (k["V"], k["d"])},
        "final_norm": {"scale": (k["d"],)},
        "stack": {"pos0": {
            "norm1": {"scale": (L, k["d"])},
            "ssd": {"a_log": (L, k["heads"]), "conv_b": (L, k["conv"]),
                    "conv_w": (L, CONV_WIDTH, k["conv"]),
                    "d_skip": (L, k["heads"]), "dt_bias": (L, k["heads"]),
                    "norm": (L, k["d_in"]),
                    "w_in": (L, k["d"], k["proj"]),
                    "w_out": (L, k["d_in"], k["d"])}}},
        "tail": [],
    }


def _leaf(name: str, shape, key, cfg: dict):
    k = dims(cfg)
    if name in ("scale", "norm", "d_skip"):
        return jnp.ones(shape, jnp.float32)
    if name == "embedding":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name == "w_in":
        return jax.random.normal(key, shape, jnp.float32) / math.sqrt(k["d"])
    if name == "w_out":
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(k["d_in"] * k["L"]))
    if name in ("conv_w", "conv_b"):
        b = 1.0 / math.sqrt(CONV_WIDTH)
        return jax.random.uniform(key, shape, jnp.float32, -b, b)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    raise KeyError(name)


def make_weights(cfg: dict):
    """``weights(key) -> params``, to be jitted: every leaf drawn from its
    own fold of the key."""
    shapes = layout(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    def weights(key):
        leaves = [_leaf(path[-1].key, shape, jax.random.fold_in(key, i), cfg)
                  for i, (path, shape) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return weights
