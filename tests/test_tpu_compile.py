"""The FL-path Pallas kernels compiled for a described TPU v5e.

No chip is attached: ``topologies.get_topology_desc`` describes a v5e 2x2
and the TPU compiler, which is installed with jax, compiles for it.  That
catches what interpret mode cannot: tiles the chip cannot align, and
kernels that use more VMEM than the chip has.  The shapes are the flat
parameter widths of the paper's client models at the paper's cohorts.

This is the only test file that describes a TPU.  Only one process may load
libtpu at a time, so the topology is described inside a fixture, on the
worker that runs these tests, and never while a module is imported.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper import CIFAR10_CNN, FEMNIST_MLP
from repro.core import flat
from repro.kernels.fedavg_momentum import fedavg_momentum_pallas
from repro.kernels.gp_projection import (gp_projection_pallas,
                                         gp_projection_softmax_pallas)
from repro.kernels.momentum import fused_momentum_pallas
from repro.models import small


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flat_width(model) -> int:
    shapes = jax.eval_shape(lambda: small.init(jax.random.key(0), model))
    return flat.make_flat_spec(shapes).padded_size


def _call(kernel, k, d):
    """(jitted kernel with interpret=False, its argument shapes)."""
    f32 = jnp.float32
    if kernel == "gp_projection":
        return (functools.partial(gp_projection_pallas, interpret=False),
                [((k, d), f32), ((d,), f32)])
    if kernel == "gp_projection_softmax":
        return (functools.partial(gp_projection_softmax_pallas,
                                  interpret=False),
                [((k, d), f32), ((d,), f32)])
    if kernel == "fedavg_momentum":
        return (functools.partial(fedavg_momentum_pallas, lr=0.005,
                                  gamma=0.1, interpret=False),
                [((k, d), f32), ((d,), f32), ((d,), f32), ((k,), f32)])
    return (functools.partial(fused_momentum_pallas, lr=0.005, gamma=0.1,
                              weight_decay=1e-4, interpret=False),
            [((d,), f32), ((d,), f32), ((d,), f32)])


@pytest.mark.parametrize("kernel", ["gp_projection", "gp_projection_softmax",
                                    "fedavg_momentum", "fused_momentum"])
@pytest.mark.parametrize("k", [5, 10])
@pytest.mark.parametrize("model", [FEMNIST_MLP, CIFAR10_CNN],
                         ids=lambda m: m.name)
def test_fl_kernel_compiles_for_v5e(one_chip, model, k, kernel):
    d = _flat_width(model)
    fn, shapes = _call(kernel, k, d)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
