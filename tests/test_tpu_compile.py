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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper import CIFAR10_CNN, FEMNIST_MLP, femnist_experiment
from repro.core import flat
from repro.fl import client
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


def _computations(hlo: str) -> dict:
    """``{name: [op lines]}`` of an HLO module's text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _reachable(comps: dict, root: str) -> list:
    """The op lines of ``root`` and of every computation it calls."""
    seen, todo, lines = set(), [root], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            lines.append(line)
            todo += re.findall(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)",
                               line)
            for group in re.findall(r"(?:branch|called)_computations=\{([^}]*)\}",
                                    line):
                todo += re.findall(r"%([\w.\-]+)", group)
    return lines


def _table_gathers(lines, width: int) -> list:
    """The gathers among ``lines`` that take ``width``-wide rows."""
    return [ln for ln in lines if " gather(" in ln and re.search(
        r"slice_sizes=\{[^}]*\b%d\}" % width, ln)]


def _loop_bodies(comps: dict) -> list:
    return [m.group(1) for lines in comps.values() for ln in lines
            for m in [re.search(r" while\(.*body=%([\w.\-]+)", ln)] if m]


@pytest.mark.parametrize("cap", [226, client.CONTRACT_MAX_ROWS + 1],
                         ids=["femnist", "taller_than_cstar"])
def test_cohort_trainer_rows_for_v5e(one_chip, monkeypatch, cap):
    """The cohort trainer at the FEMNIST MLP's shapes (K=5, batch 64, 20
    local iterations): up to ``CONTRACT_MAX_ROWS`` table rows its minibatch
    rows come from the one-hot contraction, a taller table takes one row
    gather, and either way no gather of the table is left in the local
    iterations' loop."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    exp = femnist_experiment()
    K, width = exp.clients_per_round, 784
    params = jax.eval_shape(lambda: small.init(jax.random.key(0), exp.model))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), K))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    args = [jax.tree.map(on_chip, params),
            on_chip(jax.ShapeDtypeStruct((K, cap, width), jnp.float32)),
            on_chip(jax.ShapeDtypeStruct((K, cap), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((K,), jnp.int32)), on_chip(keys)]
    hlo = client.make_cohort_trainer(exp).lower(*args).compile().as_text()
    comps = _computations(hlo)
    bodies = _loop_bodies(comps)
    assert bodies, "the local iterations compiled without a loop"
    for body in bodies:
        assert not _table_gathers(_reachable(comps, body), width)
    path = client.minibatch_path(cap)
    minibatch = [ln for ln in hlo.splitlines() if "fl_minibatch" in ln]
    gathers = _table_gathers(minibatch, width)
    if cap <= client.CONTRACT_MAX_ROWS:
        assert path == "contract"
        assert not [ln for ln in minibatch if " gather(" in ln]
        assert any(re.search(r"= f32\[%d,%d,%d\]\S* convolution" % (
            K, exp.local_iters * exp.local_batch_size, width), ln)
            for ln in minibatch)
    else:
        assert path == "take"
        assert gathers
