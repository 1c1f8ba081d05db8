"""Operations and bytes of one ``gp_projection`` call: (K, D) × (D,) → (K,).

The kernel reads the K rows and the direction once (float32) and computes
K dot products plus the direction's squared norm."""
from __future__ import annotations


def gp_bytes(k: int, d: int, itemsize: int = 4) -> int:
    """HBM bytes the call must read: K rows and the direction."""
    return (k + 1) * d * itemsize


def gp_flops(k: int, d: int) -> int:
    """Multiply-adds of the K projections and of the direction's norm."""
    return 2 * (k + 1) * d


def least_seconds(k: int, d: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for one call, and which bound
    (``"memory"`` or ``"compute"``) sets it."""
    t_mem = gp_bytes(k, d) / peak["hbm_bytes_per_s"]
    t_cmp = gp_flops(k, d) / peak["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")


def padded(d: int, pad_to: int = 128) -> int:
    """The flat workspace length the GP kernel sees for ``d`` parameters."""
    return d + (-d) % pad_to
