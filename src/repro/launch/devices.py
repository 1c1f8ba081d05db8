"""One process per TPU host: the guard for code that starts JAX children.

libtpu hands all of a host's chips to the first process that initialises
the TPU backend and holds them, behind a lock, until that process exits.
A parent that has touched the TPU therefore starves its children, and two
children started together contend for the same chips.  Code that spawns
JAX worker processes calls :func:`check_can_spawn` first; it reads the
host's PCI devices (as JAX does to decide whether a TPU is present) and
never initialises a backend itself.
"""
from __future__ import annotations

import os


def check_can_spawn(workers: int, what: str) -> None:
    """Raise unless ``workers`` concurrent JAX children can each reach
    the device: on a TPU host, the caller must not hold the backend and
    at most one child may run at a time.  Nothing is checked when the
    environment keeps JAX off the TPU (``JAX_PLATFORMS``)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return
    from jax._src import hardware_utils, xla_bridge
    chips = hardware_utils.num_available_tpu_chips_and_device_id()[0]
    if not chips:
        return
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"{what}: this process has initialised a JAX backend and so "
            f"holds the host's {chips} TPU chip(s); a child process could "
            f"not reach them. Start it from a process that has not run "
            f"JAX code.")
    if workers > 1:
        raise RuntimeError(
            f"{what}: {workers} concurrent worker processes on a host with "
            f"{chips} TPU chip(s); libtpu gives all of a host's chips to "
            f"one process, so run one worker (it can drive every chip).")
