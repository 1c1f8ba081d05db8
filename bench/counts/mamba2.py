"""Parameter count of a Mamba-2 (SSD) language model from its shapes.

Training FLOPs use the 6·N·tokens convention: N counts the tied
embedding once (it is the output head's matmul); the input lookup and
the SSD scan's own mixing are not counted, as attention's are not in
the dense-model convention."""
from __future__ import annotations

CONV_WIDTH = 4


def param_count(cfg: dict) -> int:
    """N of a Mamba-2 stack: embedding, per layer a pre-norm, the in/out
    projections, the depthwise causal conv, A, D, dt bias and the gated
    norm, then the final norm (plus an untied head where the config has
    one)."""
    d, v = cfg["d_model"], cfg["vocab_size"]
    d_in = cfg["expand"] * d
    heads = d_in // cfg["head_dim"]
    st = cfg["state_size"] * cfg.get("n_groups", 1)
    conv_dim = d_in + 2 * st
    per_layer = (d                                   # pre-norm
                 + d * (2 * d_in + 2 * st + heads)   # in_proj
                 + CONV_WIDTH * conv_dim + conv_dim  # conv weight, bias
                 + 3 * heads                         # A_log, D, dt_bias
                 + d_in                              # gated norm
                 + d_in * d)                         # out_proj
    total = v * d + cfg["n_layer"] * per_layer + d
    if not cfg.get("tie_embeddings", True):
        total += v * d
    return total


def train_flops_per_token(cfg: dict) -> int:
    """6·N: forward and backward of every parameter once per token."""
    return 6 * param_count(cfg)
