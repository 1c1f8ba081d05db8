"""Client-sharded cohort (`shard_clients`) vs the single-device scan.

The contract: sharding the flat (K, Dp) cohort matrix over a
``("clients",)`` mesh must NOT change a single decision — per-client
training and GP projections are row-independent (computed locally, then
tiled-all-gathered in single-device row order) and the server reduction
runs on the gathered replicas, so selections (and metrics) are
bit-identical to ``shard_clients=1``.

When this process already sees ≥2 jax devices (a real multi-device host,
or pytest launched under ``XLA_FLAGS=--xla_force_host_platform_device_count``)
the parity check runs in-process; otherwise it re-runs itself in a
subprocess with 2 forced host CPU devices, so the 2-device path is
exercised on every machine rather than skipped.
"""
import os
import subprocess
import sys

import jax

_PARITY_SNIPPET = r"""
import dataclasses
import numpy as np
import jax
assert jax.device_count() >= 2, f"forced host devices missing: {jax.device_count()}"
from repro.configs.paper import femnist_experiment
from repro.fl import run_experiment

def tiny(exp, rounds=5, **kw):
    return dataclasses.replace(
        exp, rounds=rounds, n_clients=16, clients_per_round=4,
        samples_per_client_mean=40, samples_per_client_std=10,
        local_iters=4, eval_size=320, **kw)

# gpfl: selection rides on GP scores + bandit state -> the strictest pin
exp = tiny(femnist_experiment("2spc", "gpfl", seed=7))
r1 = run_experiment(exp, backend="scan", param_layout="flat", shard_clients=1)
r2 = run_experiment(exp, backend="scan", param_layout="flat", shard_clients=2)
np.testing.assert_array_equal(r1.selections, r2.selections)
np.testing.assert_array_equal(r1.accuracy, r2.accuracy)
np.testing.assert_array_equal(r1.loss, r2.loss)
np.testing.assert_array_equal(r1.coverage, r2.coverage)

# the Pallas kernels inside the sharded program (interpreted here,
# Mosaic on TPU): GP scores per device, the server round per replica
k1 = run_experiment(exp, backend="scan", param_layout="flat",
                    shard_clients=1, use_gp_kernel=True)
k2 = run_experiment(exp, backend="scan", param_layout="flat",
                    shard_clients=2, use_gp_kernel=True)
np.testing.assert_array_equal(k1.selections, k2.selections)
np.testing.assert_array_equal(k1.accuracy, k2.accuracy)

# a baseline selector through the sharded path, pinned to the HOST loop
exp = tiny(femnist_experiment("2spc", "random", seed=8))
r_host = run_experiment(exp, backend="python")
r_sh = run_experiment(exp, backend="scan", param_layout="flat",
                      shard_clients=2)
np.testing.assert_array_equal(r_host.selections, r_sh.selections)
print("SHARD_PARITY_OK")
"""


def test_two_device_shard_map_cohort_bit_identical():
    """2-device shard_map cohort == single-device scan, bit for bit."""
    if jax.device_count() >= 2:
        exec(compile(_PARITY_SNIPPET, "<shard-parity>", "exec"), {})
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    proc = subprocess.run([sys.executable, "-c", _PARITY_SNIPPET],
                          env=env, capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == 0, \
        f"2-device parity subprocess failed:\n{proc.stdout}\n{proc.stderr}"
    assert "SHARD_PARITY_OK" in proc.stdout
