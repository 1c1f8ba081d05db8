"""Stage scopes and program spans (``repro.obs.trace``).

* every span name the program opens is in ``SPAN_NAMES``, and only those;
* the compiled round engine carries the six ``fl_*`` stage scopes in its
  ops' ``op_name`` metadata (tree and flat layouts, the buffered event
  scan), and the gated steps carry ``gpfl_score``/``gpfl_gate``/
  ``step_grad``/``step_update`` and the SSD's ``ssd_intra_chunk``/
  ``ssd_inter_chunk``;
* with telemetry off, a ``jax.profiler`` capture of one ``run()`` holds the
  four spans of the single-dispatch path on a host line;
* the span tracer still records the Chrome event, its args apart from the
  annotation's name.
"""
import dataclasses
import re
from pathlib import Path

import jax
import pytest

from repro.configs import ARCHS
from repro.configs.paper import femnist_experiment
from repro.dist import (init_train_state, make_gpfl_apply_step,
                        make_gpfl_train_step, make_plain_train_step)
from repro.fl.engine import ScanEngine
from repro.fl.latency import AggregationConfig
from repro.models import build, concrete_inputs
from repro.obs.trace import SPAN_NAMES, NullTracer, SpanTracer

SRC = Path(__file__).resolve().parents[1] / "src"
FL_STAGES = ("fl_select", "fl_gather", "fl_train", "fl_aggregate",
             "fl_evaluate", "fl_observe")
RUN_SPANS = ("copy_inputs", "scan_dispatch", "fetch_outputs",
             "build_result")


def _tiny(sel="gpfl", rounds=3):
    return dataclasses.replace(
        femnist_experiment("2spc", sel, seed=3), rounds=rounds,
        n_clients=12, clients_per_round=3, samples_per_client_mean=24,
        samples_per_client_std=4, local_iters=2, local_batch_size=8,
        eval_size=64)


def _op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes_in(names: set, scopes) -> set:
    """The ``scopes`` found as a path segment, bare or wrapped in
    transforms (``transpose(jvp(step_grad))``)."""
    found = set()
    for path in names:
        for seg in path.split("/"):
            for s in scopes:
                if re.fullmatch(rf"(\w+\()*{s}\)*", seg):
                    found.add(s)
    return found


def test_span_names_registry_matches_the_source():
    """Each literal ``span("...")``/``_span("...")`` in ``src/`` is a
    registered name, and each registered name is opened somewhere."""
    opened = set()
    for f in SRC.rglob("*.py"):
        opened |= set(re.findall(r'\b_?span\(\s*"([A-Za-z0-9_]+)"',
                                 f.read_text()))
    assert opened == set(SPAN_NAMES)


@pytest.mark.parametrize("kw", [
    pytest.param(dict(param_layout="tree"), id="tree"),
    pytest.param(dict(param_layout="flat"), id="flat"),
    pytest.param(dict(scenario="stragglers",
                      aggregation=AggregationConfig(kind="buffered",
                                                    buffer_size=2)),
                 id="buffered"),
])
def test_round_engine_carries_every_stage_scope(kw):
    eng = ScanEngine(_tiny(), **kw)
    names = _op_names(eng.lower().compile().as_text())
    assert _scopes_in(names, FL_STAGES) == set(FL_STAGES)


@pytest.fixture(scope="module")
def mamba():
    cfg = ARCHS["mamba2-370m"].reduced()
    api = build(cfg)
    state = init_train_state(api.init(jax.random.key(0)), 4)
    return cfg, api, state, concrete_inputs(cfg, 8, 32)


@pytest.mark.parametrize("kind", ["gpfl", "ungated", "plain", "apply"])
def test_gated_steps_carry_their_stage_scopes(mamba, kind):
    cfg, api, state, batch = mamba
    kw = dict(n_groups=4, k_select=2, total_rounds=10, lr=1e-2,
              remat="full")
    step = {"gpfl": lambda: make_gpfl_train_step(api, **kw),
            "ungated": lambda: make_gpfl_train_step(api, gate=False, **kw),
            "plain": lambda: make_plain_train_step(
                api, lr=1e-2, remat="full"),
            "apply": lambda: make_gpfl_apply_step(api, **kw)}[kind]()
    names = _op_names(jax.jit(step).lower(state, batch).compile().as_text())
    want = {"step_grad", "step_update", "ssd_intra_chunk",
            "ssd_inter_chunk"}
    if kind != "plain":
        want.add("gpfl_gate")
    if kind in ("gpfl", "ungated"):
        want.add("gpfl_score")
    every = ("gpfl_score", "gpfl_gate", "step_grad", "step_update",
             "ssd_intra_chunk", "ssd_inter_chunk")
    assert _scopes_in(names, every) == want
    # the backward pass of the rematted SSD scan keeps its scope
    assert any("ssd_inter_chunk" in n and "transpose(" in n for n in names)


def test_run_spans_land_on_the_profiler_host_plane(tmp_path):
    """telemetry='off': no Chrome tracer, but the profiler sees the four
    spans of one single-dispatch ``run()``, in order."""
    from jax.profiler import ProfileData
    eng = ScanEngine(_tiny())
    assert eng.tracer is None
    eng.run()  # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    eng.run()
    jax.profiler.stop_trace()
    xplane = next(tmp_path.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(xplane))
    host = [(e.start_ns, e.name) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name in RUN_SPANS]
    assert [n for _, n in sorted(host)] == list(RUN_SPANS)


def test_span_args_stay_in_the_chrome_event():
    tr = SpanTracer()
    with tr.span("scan_dispatch", rounds=7) as got:
        assert got is tr
    ev = tr.to_dict()["traceEvents"][-1]
    assert ev["name"] == "scan_dispatch" and ev["args"] == {"rounds": 7}
    null = NullTracer()
    with null.span("scan_dispatch", rounds=7) as got:
        assert got is null
