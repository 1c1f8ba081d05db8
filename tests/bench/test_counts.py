"""The benchmark's own operation, byte and parameter counts agree with the
program's, and an unknown chip has no peaks."""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("batch", [1, 50, 64])
def test_small_model_step_flops_equal_the_programs(batch):
    from bench.counts.small_models import flops_per_local_step
    from repro.configs.paper import CIFAR10_CNN, FEMNIST_MLP
    from repro.obs.cost import flops_per_local_step as prog
    mlp = _cfg("femnist-mlp")["model"]
    assert flops_per_local_step(mlp, batch) == prog(FEMNIST_MLP, batch)
    cnn = {"kind": "cnn", "input_shape": list(CIFAR10_CNN.input_shape),
           "conv_channels": list(CIFAR10_CNN.conv_channels),
           "fc_width": CIFAR10_CNN.fc_width,
           "num_classes": CIFAR10_CNN.num_classes}
    assert flops_per_local_step(cnn, batch) == prog(CIFAR10_CNN, batch)


def test_mlp_round_flops_and_padded_width():
    from bench.counts.gp_kernel import padded
    from bench.counts.small_models import flops_per_round, param_count
    from repro.configs.paper import FEMNIST_MLP
    from repro.models import small
    mlp = _cfg("femnist-mlp")["model"]
    assert param_count(mlp) == small.count_params(FEMNIST_MLP) == 54_112
    assert padded(param_count(mlp)) == 54_144
    # 5 clients × 20 steps × 6·53,956·64 + 2·53,956·2,000
    assert flops_per_round(mlp, 5, 20, 64, 2000) == \
        5 * 20 * 6 * 53_956 * 64 + 2 * 53_956 * 2000


def test_gp_kernel_bytes_flops_and_bound():
    from bench.counts.gp_kernel import gp_bytes, gp_flops, least_seconds
    from bench.peaks import peaks
    k, d = 5, 54_144
    assert gp_bytes(k, d) == 6 * d * 4
    assert gp_flops(k, d) == 2 * 6 * d
    t, bound = least_seconds(k, d, peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(6 * d * 4 / 819e9)


def test_mamba2_param_count_equals_the_programs():
    from bench.counts.mamba2 import param_count, train_flops_per_token
    from bench.drivers.gated_step import arch_config
    from repro.models import build
    cfg = _cfg("mamba2-370m")
    n = build(arch_config(cfg)).count_params()
    assert param_count(cfg) == n
    assert 360e6 < n < 380e6
    assert train_flops_per_token(cfg) == 6 * n


def test_mamba2_layout_equals_the_programs():
    import jax
    from bench.drivers.gated_step import arch_config
    from bench.gen.mamba2 import layout
    from repro.models import build
    cfg = _cfg("mamba2-370m")
    api = build(arch_config(cfg))
    got = jax.tree.map(lambda s: s.shape,
                       jax.eval_shape(api.init, jax.random.key(0)))
    assert got == layout(cfg)


def test_peaks_lookup():
    from bench.peaks import peaks
    row = peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
