"""FLOPs of the paper's client models (GPFL §V-B), from their shapes."""
from __future__ import annotations

import math


def macs_per_sample(model: dict) -> int:
    """Multiply-accumulates of one forward pass of one sample.

    ``model`` is the ``model`` group of a configuration file: ``kind``
    ``"mlp"`` (``input_shape``, ``hidden``, ``num_classes``) or ``"cnn"``
    (``input_shape`` H×W×C, ``conv_channels``, ``fc_width``,
    ``num_classes``; 3×3 SAME convolutions, each followed by a 2×2 max
    pool)."""
    if model["kind"] == "mlp":
        dims = ([int(math.prod(model["input_shape"]))] + list(model["hidden"])
                + [model["num_classes"]])
        return sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    if model["kind"] == "cnn":
        h, w, c_in = model["input_shape"]
        ch = [c_in] + list(model["conv_channels"])
        macs = 0
        for c0, c1 in zip(ch[:-1], ch[1:]):
            macs += 9 * c0 * c1 * h * w
            h, w = h // 2, w // 2
        macs += h * w * ch[-1] * model["fc_width"]
        macs += model["fc_width"] * model["num_classes"]
        return macs
    raise ValueError(f"unknown model kind {model['kind']!r}")


def param_count(model: dict) -> int:
    """Weights and biases of the MLP (``kind`` ``"mlp"``)."""
    if model["kind"] != "mlp":
        raise ValueError("param_count covers the MLP only")
    dims = ([int(math.prod(model["input_shape"]))] + list(model["hidden"])
            + [model["num_classes"]])
    return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))


def flops_per_local_step(model: dict, batch_size: int) -> int:
    """One client's local SGD step (forward and backward) at ``batch_size``:
    2 FLOPs per MAC forward, backward twice the forward."""
    return 6 * macs_per_sample(model) * int(batch_size)


def flops_per_round(model: dict, clients: int, local_iters: int,
                    batch_size: int, eval_size: int) -> int:
    """Model FLOPs of one synchronous FL round: K clients' local training
    plus the forward pass of the global model over the eval set."""
    return (clients * local_iters * flops_per_local_step(model, batch_size)
            + 2 * macs_per_sample(model) * int(eval_size))
