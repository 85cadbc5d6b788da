"""The reduction of a traced slice to the program's own spans and engine
scopes: innermost-span labelling of idle time, self time, launches,
scope attribution of device ops, on hand-made events and on a small
recorded chip trace; the readers of its metrics return ``None`` where
there is nothing to read."""
import gzip
import os
import shutil
import types

import pytest

from bench_tiny import BENCH, BENCHMARK
from benchlib import harness, progtrace, trace

MS = 1_000_000
DATA = os.path.join(BENCH, "tests", "data")
RECORDED = os.path.join(DATA, "trace_spans.xplane.pb.gz")
NEW_METRICS = ("sweep.params.idle_share.sweep", "sweep.rows.idle_share.sweep",
               "rounds.host.idle_share.sweep",
               "device.launches_per_point.sweep",
               "engine.deliver.busy_share.single",
               "engine.tick.busy_share.single")


def test_innermost_labels_the_span_that_opened_last():
    spans = [(0, 100, "sweep"), (10, 40, "sweep.rounds"),
             (12, 20, "round.assemble"), (20, 30, "round.wait"),
             (50, 60, "sweep.extract")]
    assert progtrace.innermost(spans) == [
        (0, 10, "sweep"), (10, 12, "sweep.rounds"),
        (12, 20, "round.assemble"), (20, 30, "round.wait"),
        (30, 40, "sweep.rounds"), (40, 50, "sweep"),
        (50, 60, "sweep.extract"), (60, 100, "sweep")]
    # a child that starts with its parent is the inner one
    assert progtrace.innermost([(0, 10, "a"), (0, 4, "b")]) == [
        (0, 4, "b"), (4, 10, "a")]


def test_reduce_spans_on_known_intervals():
    host = [(0, 0, "trace.begin"), (100 * MS, 100 * MS, "trace.end"),
            (5 * MS, 95 * MS, "run_sweep"), (10 * MS, 90 * MS, "sweep"),
            (20 * MS, 30 * MS, "sweep.params"),
            (30 * MS, 80 * MS, "sweep.rounds"),
            (30 * MS, 40 * MS, "round.assemble"),
            (40 * MS, 45 * MS, "round.launch"),
            (45 * MS, 70 * MS, "round.wait"),
            (70 * MS, 80 * MS, "rounds.final"),
            (95 * MS, 99 * MS, "sweep.params")]   # outside run_sweep
    dev = {"ops": [(42 * MS, 44 * MS, "a"), (50 * MS, 65 * MS, "b")],
           "modules": [(41 * MS, 66 * MS, "jit_x")]}
    out = progtrace.reduce_spans(host, [dev])
    assert out["window_s"] == pytest.approx(0.1)
    assert out["launches"] == 1
    assert out["count"] == {"sweep": 1, "sweep.params": 2,
                            "sweep.rounds": 1, "round.assemble": 1,
                            "round.launch": 1, "round.wait": 1,
                            "rounds.final": 1}
    assert out["self_s"] == pytest.approx({
        "sweep": 0.010 + 0.010, "sweep.params": 0.010 + 0.004,
        "round.assemble": 0.010,
        "round.launch": 0.005, "round.wait": 0.025, "rounds.final": 0.010})
    idle = out["idle"]
    assert idle == pytest.approx({
        (None, None, "between_programs"): 0.005 + 0.001,   # 0-5, 99-100
        ("run_sweep", None, "between_programs"): 0.005 + 0.005,
        ("run_sweep", "sweep", "between_programs"): 0.020,
        ("run_sweep", "sweep.params", "between_programs"): 0.010,
        ("run_sweep", "round.assemble", "between_programs"): 0.010,
        ("run_sweep", "round.launch", "between_programs"): 0.001,
        ("run_sweep", "round.launch", "in_program"): 0.002,  # 41-42, 44-45
        ("run_sweep", "round.wait", "in_program"): 0.005 + 0.001,
        ("run_sweep", "round.wait", "between_programs"): 0.004,
        ("run_sweep", "rounds.final", "between_programs"): 0.010,
        (None, "sweep.params", "between_programs"): 0.004})
    busy = 0.002 + 0.015
    assert sum(idle.values()) + busy == pytest.approx(out["window_s"])


def _fused(name, prov, opcode="add"):
    return {"name": name, "xla": {
        "expression": f"%{name} = f32[4]{{0}} {opcode}(f32[4]{{0}} %p)",
        "provenance": prov}}


def _profile():
    body = "jit(_run)/while/body/closed_call"
    deliver = _fused("fusion.1", "", "fusion")
    deliver["children"] = [
        _fused("eq.1", f"{body}/engine.deliver/eq:", "compare"),
        _fused("constant.2", f"{body}:", "constant"),
        _fused("broadcast.3", f"{body}/engine.update/broadcast_in_dim:",
               "broadcast"),
        _fused("and.4", "jit(_run)/while/cond/and:", "and"),
        _fused("add.5", f"{body}/engine.deliver/add:")]
    crossing = _fused("fusion.2", "", "fusion")
    crossing["children"] = [
        _fused("min.1", f"{body}/engine.next_event/min:", "minimum"),
        _fused("sel.2", f"{body}/engine.tick.core/vmap(one)/select_n:",
               "select")]
    dup = {"name": "fusion.3 and its duplicate(s)", "xla": {
        "expression": "%fusion.3 = ...", "provenance": ""}, "children": [
        _fused("fusion.3", f"{body}/engine.tick.l1/vmap(one)/add:"),
        _fused("fusion.4", f"{body}/engine.tick.l1/vmap(one)/add:")]}
    return {"byProgram": {"name": "by_program", "children": [
        {"name": "IDLE", "xla": {"expression": "", "provenance": ""}},
        {"name": "jit__run(1)", "children": [
            {"name": "loop fusion", "children": [deliver, crossing, dup]},
            {"name": "data formatting", "children": [
                _fused("copy.5", "", "copy"),
                _fused("reduce_min.6", "jit(_run)/while/cond/reduce_min:",
                       "reduce")]}]}]}}


def test_scope_attribution_of_fusions():
    table = progtrace.scope_map(_profile())
    assert table == {"IDLE": {}, "jit__run(1)": {
        "fusion.1": "engine.deliver",      # moves and the loop's condition
                                           # do not decide
        "fusion.2": progtrace.MIXED,       # crosses two scopes
        "fusion.3": "engine.tick.l1", "fusion.4": "engine.tick.l1",
        "copy.5": progtrace.UNSCOPED,      # made by the compiler
        "reduce_min.6": progtrace.UNSCOPED}}   # the loop's condition
    dev = {"ops": [(0, 4, "fusion.1"), (4, 6, "fusion.2"),
                   (6, 7, "fusion.3"), (7, 8, "copy.5"), (8, 9, "other")],
           "modules": [(0, 10, "jit__run(1)")]}
    busy = progtrace.scope_busy([dev], 1, 10, table)
    assert busy == pytest.approx({
        "engine.deliver": 3e-9, progtrace.MIXED: 2e-9,
        "engine.tick.l1": 1e-9, progtrace.UNSCOPED: 2e-9})


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded chip trace, unpacked where ``op_profile`` may write
    its ``ALL_HOSTS.op_stats.pb`` beside it."""
    logdir = tmp_path_factory.mktemp("trace_spans")
    run = logdir / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    with gzip.open(RECORDED, "rb") as src, \
            open(run / "spans.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(logdir)


def test_recorded_chip_trace_spans(recorded):
    """A 4-core memsys run to 200 cycles and a 4-point Onira campaign to
    60 cycles, traced on one TPU v5e (``record_trace.py``)."""
    out = progtrace.summarize(trace.find_xplane(recorded))
    for name in ("engine.init_state", "engine.run", "sweep", "sweep.build",
                 "sweep.params", "sweep.rounds", "sweep.transfer",
                 "sweep.extract", "round.assemble", "round.launch",
                 "round.wait", "round.harvest", "rounds.final"):
        assert out["count"].get(name) == 1, name
    base = trace.summarize(recorded, harness.SPANS)
    assert out["window_s"] == pytest.approx(base["window_s"])
    assert sum(out["idle"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])
    assert out["launches"] >= 2


def test_recorded_chip_trace_scopes(recorded):
    busy = progtrace.scopes(trace.find_xplane(recorded))
    base = trace.summarize(recorded, harness.SPANS)
    assert sum(busy.values()) == pytest.approx(base["busy_s"], rel=0.01)
    assert {"engine.next_event", "engine.deliver", "engine.update"} <= \
        set(busy)
    assert any(k.startswith("engine.tick.") for k in busy)
    assert not os.path.exists(os.path.join(os.path.dirname(RECORDED),
                                           "ALL_HOSTS.op_stats.pb"))


def _run(cell, logdir=None, monkeypatch=None):
    run = types.SimpleNamespace(
        cell=harness.Cell(BENCHMARK, cell), trace=None)
    if logdir is not None:
        run.trace = {"busy_s": 1.0, "window_s": 1.0}
        monkeypatch.setattr(progtrace, "logdir", lambda r: logdir)
    return run


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_none_without_a_trace(name):
    metric = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    (cell,) = metric["workloads"]
    assert harness.load_module("metrics", name).read(_run(cell)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_readers_return_none_on_a_program_without_spans(
        name, tmp_path, monkeypatch):
    """The trace of a program that opens none of these spans and names
    no scope (the earlier recording) reads as nothing, not as 0."""
    shutil.copytree(os.path.join(DATA, "trace_tiny"), tmp_path / "t")
    metric = next(m for m in BENCHMARK["per_layer"] if m["name"] == name)
    (cell,) = metric["workloads"]
    run = _run(cell, str(tmp_path / "t"), monkeypatch)
    assert harness.load_module("metrics", name).read(run) is None


def test_new_readers_read_the_recorded_trace(recorded, monkeypatch):
    onira = _run("onira-grid-sweep", recorded, monkeypatch)
    onira.cell.traffic = dict(onira.cell.traffic, points=4)
    memsys = _run("memsys64-mixed-single", recorded, monkeypatch)
    got = {name: harness.load_module("metrics", name).read(
        memsys if name.endswith(".single") else onira)
        for name in NEW_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert 0 < got["engine.deliver.busy_share.single"] < 100
    assert 0 < got["engine.tick.busy_share.single"] < 100
    assert got["device.launches_per_point.sweep"] > 0
