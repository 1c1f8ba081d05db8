"""Where JAX's persistent compilation cache lives, for the entry points.

The cache is keyed by its path as well as by the program, so it must sit
at a path that does not move between runs.  ``JAX_COMPILATION_CACHE_DIR``
places it from outside (JAX reads the variable itself); otherwise it goes
to ``.jax_cache`` at the root of this checkout.  Called by entry points
(``chip_smoke.py``, ``repro.launch.train``, the sweep worker,
``benchmarks.run``), never on ``import repro``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory, used when the environment names none.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
