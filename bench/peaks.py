"""The benchmark's own table of chip peaks, keyed by JAX's ``device_kind``.

A device kind missing from ``peaks.json`` is an error, never a default:
a share of a peak that the table does not hold cannot be computed.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; raises ``KeyError`` if unknown."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
