"""The trace reduction: device busy time, top device ops and idle gaps
by host span, on hand-made events and on a small recorded trace."""
import os

import pytest

from bench_tiny import BENCH
from benchlib import harness, trace

MS = 1_000_000


def test_reduce_events_on_known_intervals():
    host = [(0, 100 * MS, "window"), (10 * MS, 60 * MS, "sim.run"),
            (60 * MS, 70 * MS, "extract"), (-50 * MS, 0, "setup")]
    dev = {"ops": [(-5 * MS, 5 * MS, "fusion.1"),     # clipped to 5 ms
                   (20 * MS, 30 * MS, "fusion.2"),
                   (25 * MS, 35 * MS, "fusion.2"),    # overlaps: busy 15
                   (40 * MS, 50 * MS, "while.3")],
           "modules": [(15 * MS, 55 * MS)]}
    out = trace.reduce_events(host, [dev], harness.SPANS)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["busy_s"] == pytest.approx(0.030)
    ops = dict(out["device_ops"])
    assert ops == pytest.approx({"fusion.2": 0.020, "while.3": 0.010,
                                 "fusion.1": 0.005})
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({
        "window:between_programs": 0.005 + 0.030,   # 5-10, 70-100
        "sim.run:between_programs": 0.005 + 0.005,  # 10-15, 55-60
        "sim.run:in_program": 0.015,        # 15-20, 35-40, 50-55
        "extract:between_programs": 0.010})         # 60-70
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(0.1)


def test_reduce_events_averages_devices():
    host = [(0, 10 * MS, "window")]
    devs = [{"ops": [(0, 10 * MS, "a")], "modules": []},
            {"ops": [(0, 5 * MS, "a")], "modules": []}]
    out = trace.reduce_events(host, devs, harness.SPANS)
    assert out["busy_s"] == pytest.approx(0.0075)
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"window:between_programs": 0.0025})


def test_reduce_events_needs_a_window():
    with pytest.raises(ValueError):
        trace.reduce_events([], [{"ops": [], "modules": []}], harness.SPANS)


RECORDED = os.path.join(BENCH, "tests", "data", "trace_tiny")


def test_recorded_chip_trace():
    """A 4-core memsys run to 200 cycles (45 epochs) in a ``window``
    span, traced on one TPU v5e: 12719 op events, of which the
    ``while`` and ``conditional`` ops enclose the rest."""
    out = trace.summarize(RECORDED, harness.SPANS)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.071740188)
    # the innermost ops alone: the while loop spans 3.3 ms, its body's
    # ops 2.6 ms of it
    assert out["busy_s"] == pytest.approx(0.002640595)
    names = [n for n, _ in out["device_ops"]]
    assert not any(n.startswith(("while", "cond")) for n in names)
    gaps = dict(out["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert gaps["sim.run:in_program"] > 0


def test_leaves_drop_enclosing_control_flow():
    evs = [(0, 100, "while.1"), (0, 40, "cond.2"), (0, 10, "fusion.3"),
           (20, 30, "fusion.4"), (50, 60, "fusion.5"), (200, 210, "copy")]
    assert [n for _, _, n in trace.leaves(evs)] == [
        "fusion.3", "fusion.4", "fusion.5", "copy"]
    assert trace.op_name("%fusion.6 = s32[4225]{0} fusion(x)") == \
        "fusion.6"


def test_slice_marks_bound_the_window():
    host = [(0, 100 * MS, "window"), (30 * MS, 30 * MS, "trace.begin"),
            (50 * MS, 50 * MS, "trace.end")]
    dev = {"ops": [(20 * MS, 40 * MS, "a")], "modules": []}
    out = trace.reduce_events(host, [dev], harness.SPANS)
    assert out["window_s"] == pytest.approx(0.020)
    assert out["busy_s"] == pytest.approx(0.010)
