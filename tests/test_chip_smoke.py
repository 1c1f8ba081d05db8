"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script itself refuses every platform but TPU; these tests drive its
phase functions directly, the way it drives them on the chip.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs.paper import femnist_experiment  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.utils import compile_cache  # noqa: E402


def _tiny_exp(k=4):
    return dataclasses.replace(
        femnist_experiment("2spc", "gpfl", rounds=3), n_clients=12,
        clients_per_round=k, samples_per_client_mean=30,
        samples_per_client_std=8, local_iters=2, local_batch_size=16,
        eval_size=200)


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep the persistent cache of this test process as it was."""
    monkeypatch.setattr(train, "enable_compile_cache", lambda: "")


def test_refuses_cpu_and_names_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_fl_phase_scan_matches_host_oracle(capsys):
    chip_smoke.fl_phase(_tiny_exp())
    assert "scan selection history == host oracle" in capsys.readouterr().out


def test_gated_phase_matches_plain_step(no_compile_cache, capsys):
    chip_smoke.gated_phase("mamba2-370m", reduce=True, batch=8, seq=32,
                           n_groups=4, k_select=2, steps=2, remat="full")
    assert "vs plain step" in capsys.readouterr().out


def test_fl_phase_fails_on_failed_cell(monkeypatch):
    from repro.fl import simulation

    def broken(*a, **k):
        raise RuntimeError("host loop broke")

    monkeypatch.setattr(simulation, "run_python_loop", broken)
    with pytest.raises(chip_smoke.SmokeFailure, match="host loop broke"):
        chip_smoke.fl_phase(_tiny_exp())


SHARDED = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, sys.argv[1])
    import chip_smoke
    sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
    from test_chip_smoke import _tiny_exp
    chip_smoke.sharded_phase(_tiny_exp(k=8), 4)
""")


def test_sharded_phase_on_four_host_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED, ROOT],
                         capture_output=True, text=True, env=env,
                         timeout=400)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "each of 4 devices trains 2 of the" in out.stdout
    assert "selection history == shard_clients=1" in out.stdout


def test_compile_cache_placement(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_last_line_contract(monkeypatch, capsys):
    """On a (stubbed) TPU device the last stdout line is exactly the
    contract's JSON object."""

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    monkeypatch.setattr(chip_smoke, "fl_phase", lambda exp: None)
    monkeypatch.setattr(chip_smoke, "gated_phase", lambda *a, **k: None)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
