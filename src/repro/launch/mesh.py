"""Production mesh construction and the per-chip peaks the roofline uses.

Target hardware: TPU v5e pods — 256 chips/pod (16×16).  Mesh builders are
FUNCTIONS (not module-level constants) so importing this module never
touches jax device state.

Every mesh is built with ``AxisType.Auto`` axes: the models place
activations with ``with_sharding_constraint`` (``models.common.shard``),
which is a hint under ``Auto`` but an assert under the ``Explicit`` axes
``jax.make_mesh`` builds by default.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_cpu_test_mesh(data: int = 2, model: int = 2, *,
                       multi_pod: bool = False):
    """Small mesh over however many (forced-host) devices tests configured."""
    if multi_pod:
        return _make_mesh((2, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))


#: Published per-chip peaks keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 links
#: (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s_per_link": 50e9},
}


def device_peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row of one device kind; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known kinds: {sorted(PEAKS)}") from None
