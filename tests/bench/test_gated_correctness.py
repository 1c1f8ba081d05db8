"""The gated cells' correctness check at a CPU size: a sound run passes,
and the control and every fault the cell can have fail it."""
import pytest

from cells import run_tiny, tiny_cell

CELL = "gated-mamba2-370m-gpfl"


@pytest.mark.parametrize("cell", [CELL, "gated-mamba2-370m-plain"])
def test_sound_run_is_correct(cell):
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_control_fails():
    from bench.drivers.gated_step import Driver
    wl, cfg = tiny_cell(CELL)
    drv = Driver(cfg, wl, 7)
    drv.setup()
    drv.release()
    got = drv.control_readings()
    assert any(got[k] > v for k, v in wl["limits"].items()), got


def _patch(monkeypatch, fault):
    import repro.dist as dist
    from repro.dist import gpfl_step
    real_factory = dist.make_gpfl_train_step
    if fault == "answer":  # the gate's selection altered where it is made
        real_select = gpfl_step._select

        def altered(*a, **kw):
            mask, u = real_select(*a, **kw)
            return 1.0 - mask, u
        monkeypatch.setattr(gpfl_step, "_select", altered)
        return

    def factory(*a, **kw):
        real = real_factory(*a, **kw)
        if fault == "unchanged":
            def step(state, batch):
                _, met = real(state, batch)
                return state, met
        else:  # half of the batch left out, the mean over the rest
            def step(state, batch):
                return real(state, {k: v[::2] for k, v in batch.items()})
        return step
    monkeypatch.setattr(dist, "make_gpfl_train_step", factory)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "answer"])
def test_broken_program_is_not_correct(monkeypatch, fault):
    _patch(monkeypatch, fault)
    res = run_tiny(CELL)
    assert not res["correct"], res["checks"]
