"""The trace reduction: busy time is the union of device op intervals
inside the window, overlapping ops count once, a loop counts through the
ops inside it, and each idle gap takes the name of the innermost
benchmark span that covers it."""
from pathlib import Path

import pytest

from bench.trace_reduce import reduce_events, union_length

HOST = [("window", 0, 1000), ("dispatch", 0, 600), ("next_batch", 600, 700),
        ("wait", 700, 1000), ("PjitFunction(step)", 10, 20)]


def test_union_counts_overlap_once_and_clips_to_window():
    covered, merged = union_length([(0, 50), (40, 100), (90, 120),
                                    (300, 400), (950, 1200)], 0, 1000)
    assert merged == [[0, 120], [300, 400], [950, 1000]]
    assert covered == 120 + 100 + 50


def test_reduce_busy_idle_ops_and_gap_labels():
    # a while loop (100-400) holding two fusions, then two more ops
    ops = {"/device:TPU:0": [("while.3", 100, 400), ("fusion.1", 120, 200),
                             ("fusion.2", 250, 380), ("_kernel", 650, 680),
                             ("fusion.1", 720, 900)]}
    red = reduce_events(ops, HOST, {"dispatch", "next_batch", "wait"})
    assert red["window_s"] == pytest.approx(1000e-9)
    # the loop counts through the two fusions inside it, not its interval
    assert red["busy_s"] == pytest.approx((80 + 130 + 30 + 180) * 1e-9)
    assert red["idle_share"] == pytest.approx(1 - 0.42)
    # self time: the loop's 300 less its two fusions' 80 + 130
    assert red["op_seconds"]["while.3"] == pytest.approx(90e-9)
    assert red["op_seconds"]["fusion.1"] == pytest.approx(260e-9)
    assert red["op_counts"]["fusion.1"] == 2
    # gaps [0,120] [200,250] [380,650] [680,720] [900,1000], the loop's
    # time outside its fusions among them; 700 lies in both next_batch
    # and wait, and the shorter span names it
    labels = sorted((round(s * 1e9), label) for label, s in red["gaps"])
    assert labels == [(40, "next_batch"), (50, "dispatch"), (100, "wait"),
                      (120, "dispatch"), (270, "dispatch")]
    assert red["gaps"][0] == ("dispatch", pytest.approx(270e-9))


def test_op_names_are_cut_from_hlo_text():
    from bench.trace_reduce import op_name
    assert op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
                   ) == "fusion.12"
    assert op_name("copy-start.3") == "copy-start.3"


def test_gap_outside_any_benchmark_span_and_two_devices():
    ops = {"/device:TPU:0": [("a", 0, 500)],
           "/device:TPU:1": [("a", 0, 1000)]}
    red = reduce_events(ops, [("window", 0, 1000), ("other", 0, 1000)],
                        {"dispatch"})
    assert red["n_devices"] == 2
    assert red["busy_s"] == pytest.approx(750e-9)
    assert red["gaps"] == [("none", pytest.approx(500e-9))]


def test_window_span_is_required():
    with pytest.raises(ValueError):
        reduce_events({"/device:TPU:0": [("a", 0, 1)]}, [("dispatch", 0, 1)])


DATA = Path(__file__).parent / "data" / "fl_small.xplane.pb"


def _sweep_busy(ops, lo, hi):
    """Busy time by a sweep over interval edges (an independent check of
    the merge in ``union_length``)."""
    edges = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for _, s, e in ops
                      if e > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_chip_trace():
    """A trace recorded on one v5e chip: the FL gpfl cell cut to 4 rounds,
    two ``ScanEngine.run()`` dispatches inside the benchmark's spans."""
    from bench.trace_reduce import leaf_ops, read_xplane
    dev, host = read_xplane(str(DATA))
    assert list(dev) == ["/device:TPU:0"]
    red = reduce_events(dev, host, {"dispatch"})
    lo, hi = next((s, e) for n, s, e in host if n == "window")
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    ops = dev["/device:TPU:0"]
    leaves = leaf_ops(ops)
    assert {n.split(".")[0] for n in
            {o[0] for o in ops} - {o[0] for o in leaves}} >= {"while"}
    assert red["busy_s"] == pytest.approx(_sweep_busy(leaves, lo, hi) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(s for _, s in red["gaps"]) + red["busy_s"] == \
        pytest.approx(red["window_s"])
    assert {label for label, _ in red["gaps"]} <= {"dispatch", "none"}
    # self times partition the time covered by any op, loops included,
    # which holds the leaf ops' busy time
    every = _sweep_busy(ops, lo, hi) * 1e-9
    assert sum(red["op_seconds"].values()) == pytest.approx(every)
    assert red["busy_s"] < every
    # one GP kernel call per round: 4 rounds × 2 dispatches
    gp = [n for n in red["op_counts"] if n.startswith("gp_projection.")]
    assert sum(red["op_counts"][n] for n in gp) == 8
