"""Stage scopes and program spans read from a profiler trace
(``bench.trace_paths``): on plain data, on the recorded trace of the
program before it had scopes, and on a recorded trace of the scoped
program."""
from pathlib import Path

import pytest

from bench import trace_paths as tp
from bench.trace_reduce import leaf_ops, read_xplane, reduce_events

DATA = Path(__file__).parent / "data"
UNSCOPED = DATA / "fl_small.xplane.pb"
SCOPED = DATA / "fl_scoped.xplane.pb"


# ------------------------------------------------------------ plain data

@pytest.mark.parametrize("path, name, found", [
    ("jit(f)/fl_train/vmap()/dot_general", "fl_train", True),
    ("jit(f)/transpose(jvp(step_grad))/dot_general", "step_grad", True),
    ("jit(f)/jvp(gpfl_score)/checkpoint/mul", "gpfl_score", True),
    ("jit(f)/step_gradient/mul", "step_grad", False),
    ("jit(f)/fl_train_x/mul", "fl_train", False),
    ("jit(f)/jit(fl_train)/mul", "fl_train", True),
    ("", "fl_train", False),
])
def test_scope_is_a_whole_segment_maybe_wrapped(path, name, found):
    assert tp.has_scope(path, name) is found


def test_innermost_stage_and_partition():
    paths = {"jit(s)/while/body/fl_train/dot": 5.0,
             "jit(s)/while/body/fl_train/fl_observe/gp_projection": 1.0,
             "jit(s)/while/body/transpose(jvp(fl_select))/sort": 2.0,
             "jit(s)/while": 0.5, "": 0.25}
    st = tp.stage_seconds(paths, tp.FL_STAGES)
    assert st["fl_train"] == 5.0 and st["fl_observe"] == 1.0
    assert st["fl_select"] == 2.0 and st[None] == 0.75
    assert sum(st.values()) == pytest.approx(sum(paths.values()))
    red = {"path_seconds": paths}
    us = tp.fl_stage_us(red, rounds=2)
    assert us == {"select": 1e6, "gather": 0.0, "train": 2.5e6,
                  "aggregate": 0.0, "evaluate": 0.0, "observe": 5e5}
    assert tp.unscoped_share(red, tp.FL_STAGES) == pytest.approx(
        100 * 0.75 / 8.75)


def test_step_stages_count_ssd_inter_chunk_wherever_nested():
    paths = {"jit(step)/gpfl_score/jvp(ssd_inter_chunk)/while": 3.0,
             "jit(step)/gpfl_score/jvp(ssd_intra_chunk)/dot": 1.0,
             "jit(step)/step_grad/transpose(jvp(ssd_inter_chunk))/mul": 4.0,
             "jit(step)/step_grad/transpose(jvp())/dot": 6.0,
             "jit(step)/gpfl_gate/sort": 0.001,
             "jit(step)/step_update/add": 0.5, "jit(step)/copy": 0.5}
    ms = tp.step_stage_ms({"path_seconds": paths}, steps=2)
    assert ms["score"] == pytest.approx(2000.0)
    assert ms["grad"] == pytest.approx(5000.0)
    assert ms["update"] == pytest.approx(250.0)
    assert ms["gate"] == pytest.approx(0.5)
    assert ms["ssd_inter_chunk"] == pytest.approx(3500.0)
    assert tp.unscoped_share({"path_seconds": paths}, tp.STEP_STAGES) == \
        pytest.approx(100 * 0.5 / sum(paths.values()))


def test_readers_return_nothing_without_scopes_or_spans():
    """A program without stage scopes or engine spans (a build from
    before they existed) gives no value, not a zero or a 100% share."""
    red = {"path_seconds": {"jit(run_scan)/while/body/dot": 1.0, "": 0.1},
           "host_spans": [("dispatch", 0, 10)], "idle_intervals": [(2, 4)],
           "n_devices": 1}
    assert tp.fl_stage_us(red, 10) == {}
    assert tp.step_stage_ms(red, 10) == {}
    assert tp.unscoped_share(red, tp.FL_STAGES) is None
    assert tp.host_idle_ms_per_dispatch(red) is None


def test_idle_goes_to_the_innermost_program_span_cut_at_span_edges():
    spans = [("dispatch", 0, 1000), ("copy_inputs", 0, 300),
             ("scan_dispatch", 200, 900), ("fetch_outputs", 900, 950),
             ("build_result", 950, 990)]
    gaps = [(0, 400), (880, 1000)]
    idle = tp.span_idle(gaps, spans, set(tp.RUN_SPANS))
    # 0-300 copy_inputs (shorter than scan_dispatch over 200-300),
    # 300-400 and 880-900 scan_dispatch, then fetch, build, none
    assert idle["copy_inputs"] == pytest.approx(300e-9)
    assert idle["scan_dispatch"] == pytest.approx(120e-9)
    assert idle["fetch_outputs"] == pytest.approx(50e-9)
    assert idle["build_result"] == pytest.approx(40e-9)
    assert idle[None] == pytest.approx(10e-9)
    red = {"host_spans": spans, "idle_intervals": gaps, "n_devices": 1}
    assert tp.host_idle_ms_per_dispatch(red) == pytest.approx(390e-6)
    assert tp.largest_gaps(gaps, spans, set(tp.RUN_SPANS), top=1)[0][0] \
        == pytest.approx(400e-9)


def test_a_pathless_loop_takes_the_path_its_body_shares():
    ops = [("while.1", 0, 100), ("f.1", 10, 20), ("while.2", 30, 90),
           ("f.2", 40, 50), ("f.3", 60, 70)]
    paths = ["", "s/body/fl_select/sort", "", "s/body/fl_train/a/dot",
             "s/body/fl_train/b/add"]
    got = tp.loop_paths(ops, paths)
    assert got == ["s/body", "s/body/fl_select/sort", "s/body/fl_train",
                   "s/body/fl_train/a/dot", "s/body/fl_train/b/add"]
    st = tp.stage_seconds(tp.path_seconds({"d": ops}, {"d": paths}, 0, 100),
                          tp.FL_STAGES)
    # the inner loop's own 40 ns go to fl_train, the outer loop's 30 ns
    # (its body spans two stages) to none
    assert st["fl_train"] == pytest.approx(60e-9)
    assert st[None] == pytest.approx(30e-9)


def test_path_seconds_are_the_self_times_of_reduce_events():
    ops = {"/device:TPU:0": [("while.3", 100, 400), ("fusion.1", 120, 200),
                             ("fusion.2", 250, 380), ("k", 650, 680),
                             ("fusion.1", 720, 1200)]}
    paths = {"/device:TPU:0": ["jit(s)/while", "jit(s)/while/body/fl_train",
                               "jit(s)/while/body/fl_observe", "",
                               "jit(s)/fl_train"]}
    host = [("window", 0, 1000), ("copy_inputs", 0, 50)]
    red = reduce_events(ops, host)
    got = tp.reduce_paths(ops, host, paths)
    assert got["path_seconds"] == {
        "jit(s)/while": pytest.approx(90e-9),
        "jit(s)/while/body/fl_train": pytest.approx(80e-9),
        "jit(s)/while/body/fl_observe": pytest.approx(130e-9),
        "": pytest.approx(30e-9), "jit(s)/fl_train": pytest.approx(280e-9)}
    assert sum(got["path_seconds"].values()) == \
        pytest.approx(sum(red["op_seconds"].values()))
    assert got["host_spans"] == [("copy_inputs", 0, 50)]
    assert sum(e - s for s, e in got["idle_intervals"]) * 1e-9 == \
        pytest.approx(red["window_s"] - red["busy_s"])


# ------------------------------------------------------ recorded traces

def _in_window(ops, host):
    lo, hi = tp.window_of(host)
    return [o for o in ops if o[2] > lo and o[1] < hi]


@pytest.mark.parametrize("trace", [UNSCOPED, SCOPED], ids=lambda p: p.stem)
def test_recorded_trace_paths_align_with_its_ops(trace):
    """Every event of the ops line gets its metadata's path, in the order
    ``read_xplane`` lists the events; the self time by path sums to the
    self time by op name; every fusion and the GP kernel carry a path
    (the events without one are ops the compiler adds)."""
    dev, host = read_xplane(str(trace))
    paths = tp.read_op_paths(str(trace))
    assert set(paths) == set(dev) == {"/device:TPU:0"}
    ops, pp = dev["/device:TPU:0"], paths["/device:TPU:0"]
    assert len(pp) == len(ops)
    red = reduce_events(dev, host)
    got = tp.reduce_paths(dev, host, paths)
    assert sum(got["path_seconds"].values()) == \
        pytest.approx(sum(red["op_seconds"].values()), rel=1e-12)
    path_of = {id(o): p for o, p in zip(ops, pp)}
    leaves = _in_window(leaf_ops(ops), host)
    assert leaves
    added = ("copy", "copy-start", "copy-done", "custom-call",
             "dynamic-update-slice", "while", "iota", "convert")
    for o in leaves:
        kind = o[0].rsplit(".", 1)[0]
        assert path_of[id(o)] or kind in added, o
        if "fusion" in kind or kind == "gp_projection":
            assert path_of[id(o)], o
    # the one Pallas kernel marks the alignment: its events, and only
    # they, carry a pallas_call path
    assert any(o[0].startswith("gp_projection.") for o in leaves)
    assert all(o[0].startswith("gp_projection.") == p.endswith("pallas_call")
               for o, p in zip(ops, pp) if p)


def test_unscoped_recorded_trace_reads_no_stage():
    dev, host = read_xplane(str(UNSCOPED))
    red = tp.reduce_paths(dev, host, tp.read_op_paths(str(UNSCOPED)))
    assert tp.fl_stage_us(red, 8) == {}
    assert tp.unscoped_share(red, tp.FL_STAGES) is None
    assert tp.host_idle_ms_per_dispatch(red) is None


def test_scoped_recorded_trace_finds_every_stage():
    """One v5e chip, the FL gpfl cell cut to 25 rounds a dispatch, two
    ``ScanEngine.run()`` dispatches: every stage holds device time, the
    GP kernel keeps its name and sits under ``fl_observe``, and the
    engine's spans label idle time.  What lies under no stage (5.96% of
    the self time here) is the round loop's own machinery, none of it
    inside the round body: the loop, the scan's slices and output writes
    and carry copies (which carry the loop's path), and the per-dispatch
    cast of the client tables (which carries none)."""
    dev, host = read_xplane(str(SCOPED))
    red = tp.reduce_paths(dev, host, tp.read_op_paths(str(SCOPED)))
    us = tp.fl_stage_us(red, 50)
    assert set(us) == {s[3:] for s in tp.FL_STAGES}
    assert all(v > 0 for v in us.values()), us
    assert us["train"] == max(us.values())
    unscoped = [p for p, secs in red["path_seconds"].items()
                if secs > 0 and tp.innermost(p, tp.FL_STAGES) is None]
    assert unscoped and not [p for p in unscoped if "closed_call/" in p]
    assert tp.unscoped_share(red, tp.FL_STAGES) < 6.0
    gp = [p for p, s in red["path_seconds"].items()
          if p.endswith("gp_projection/pallas_call")]
    assert gp and all(tp.innermost(p, tp.FL_STAGES) == "fl_observe"
                      for p in gp)
    ops = dev["/device:TPU:0"]
    assert sum(o[0].startswith("gp_projection.")
               for o in _in_window(ops, host)) == 50
    assert {n for n, _, _ in red["host_spans"]} >= set(tp.RUN_SPANS)
    assert tp.host_idle_ms_per_dispatch(red) > 0


def test_stage_report_of_the_scoped_trace():
    """``bench/stage_report.py``'s breakdown: stage times and the share
    under none account for the device self time per round."""
    from bench.stage_report import report
    dev, host = read_xplane(str(SCOPED))
    out = report(dev, host, tp.read_op_paths(str(SCOPED)), unit="rounds",
                 units=50, steps=2, span_names={"dispatch"})
    per_round_us = 1e3 * out["self_ms_per_step"] * 2 / 50
    scoped = sum(out["fl_stage_us"].values())
    assert scoped / per_round_us == pytest.approx(
        1 - out["unscoped_share.fl"] / 100)
    assert sum(out["idle_ms_by_span"].values()) * 1e-3 == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert out["host_idle_ms_per_dispatch"] == tp.host_idle_ms_per_dispatch(
        tp.reduce_paths(dev, host, tp.read_op_paths(str(SCOPED))))
