"""The FL cell's correctness check at a CPU size: a sound run passes, and
the control and every fault the cell can have fail it."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from cells import run_tiny, tiny_cell

CELL = "fl-femnist-2spc-gpfl"
#: the driver with the paper's random baseline in place of GPFL
RANDOM = dict(selector="random", limits={"selection": 0.0, "loss": 0.04})


@pytest.mark.parametrize("selector", ["gpfl", "random"])
def test_sound_run_is_correct(selector):
    res = run_tiny(CELL, **(RANDOM if selector == "random" else {}))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"rounds_per_s", "setup_s"}


def test_control_fails():
    from bench.drivers.fl_scan import Driver
    wl, cfg = tiny_cell(CELL)
    drv = Driver(cfg, wl, 7)
    drv.setup()
    drv.release()
    got = drv.control_readings()
    assert any(got[k] > v for k, v in wl["limits"].items()), got


def _frozen_trainer(exp):
    def one(params, x, y, size, rng):
        return params, jax.tree.map(jnp.zeros_like, params), jnp.zeros(())
    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0, 0)))


def _half_cohort_fedavg(cohort_params, weights=None):
    """FedAvg over the first half of the round's client updates."""
    from repro.fl.server import fedavg
    k = jax.tree.leaves(cohort_params)[0].shape[0]
    return fedavg(jax.tree.map(lambda a: a[:k - k // 2], cohort_params))


def _half_gp(stacked_grads, direction):
    """GP scores projected over the first half of each leaf only."""
    from repro.utils.pytree import tree_global_norm

    def half(a):
        return a.reshape(a.shape[0], -1)[:, :max(a[0].size // 2, 1)]
    dn = tree_global_norm(direction)
    dots = sum(jnp.einsum("kd,d->k", half(g), half(d[None])[0])
               for g, d in zip(jax.tree.leaves(stacked_grads),
                               jax.tree.leaves(direction)))
    return dots / jnp.maximum(dn, 1e-12)


def _patch(monkeypatch, fault):
    import repro.fl.engine as engine
    from repro.core import gp, gpcb
    if fault == "unchanged":
        monkeypatch.setattr(engine, "make_cohort_trainer", _frozen_trainer)
    elif fault == "half_cohort":  # the server's batch: the round's cohort
        monkeypatch.setattr(engine, "fedavg", _half_cohort_fedavg)
    elif fault == "half_batch":  # each client's local minibatch
        real = engine.make_cohort_trainer
        monkeypatch.setattr(engine, "make_cohort_trainer", lambda exp: real(
            dataclasses.replace(exp,
                                local_batch_size=exp.local_batch_size // 2)))
    elif fault == "gp":  # the GP scores of the cohort, where they are made
        monkeypatch.setattr(gp, "gp_scores_stacked", _half_gp)
    else:  # the cohort altered where it is chosen: the best client is lost
        real_sel = gpcb.selection_scores

        def altered(*a, **kw):
            s = real_sel(*a, **kw)
            return s.at[jnp.argmax(s)].set(-jnp.inf)
        monkeypatch.setattr(gpcb, "selection_scores", altered)


@pytest.mark.parametrize("fault", ["unchanged", "half_cohort", "half_batch",
                                   "answer", "gp"])
def test_broken_program_is_not_correct(monkeypatch, fault):
    _patch(monkeypatch, fault)
    res = run_tiny(CELL)
    assert not res["correct"], res["checks"]
