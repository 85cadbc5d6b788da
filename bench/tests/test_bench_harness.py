"""The benchmark's harness: its files are found by name, its inputs are
deterministic in the seed, it refuses to run without a TPU, and a run at
a tiny size on the CPU checks out as correct."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import BENCH, BENCHMARK, CELLS, ROOT, SEED, run_tiny, \
    tiny_cell
from benchlib import harness, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_the_contract():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert 1 <= b["run_seconds"] <= 51
    # a full check of 24 cells still fits its 43200 s
    cells = 24
    assert (2 + 14 * cells) * (b["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in b["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in \
        e2e["setup_s"]
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in moved.get("workloads", CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = harness.Cell(BENCHMARK, name)
    assert hasattr(harness.load_module("drivers", cell.traffic["driver"]),
                   "Driver")
    assert hasattr(harness.load_module("systems", cell.system), "System")
    ref = harness.load_module("reference", cell.system)
    assert callable(ref.simulate) and callable(ref.inputs)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_module("metrics", m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} > {"setup_s"}
    assert cell.per_layer


@pytest.mark.parametrize("name", CELLS)
def test_inputs_are_deterministic_in_the_seed(name):
    cell = tiny_cell(name)
    ref = harness.load_module("reference", cell.system)

    def draw(seed, k):
        return (ref.inputs(cell.config, traffic.job_rng(seed, k)),
                traffic.points(cell.traffic, traffic.job_seed(seed, k))
                if cell.traffic["driver"] == "sweep" else None)

    assert draw(SEED, 3) == draw(SEED, 3)
    assert traffic.job_seed(SEED, 3) != traffic.job_seed(SEED + 1, 3)
    if cell.system == "memsys" or cell.traffic.get("sampler") == "random":
        assert draw(SEED, 3) != draw(SEED + 1, 3)


def test_random_sampler_is_the_programs():
    from repro.dse import SweepSpec
    axes = {"conn_latency": (1, 100), "kind.cpu.flush_cycles": (1, 8),
            "kind.l1.extra_hit_rate": (0.0, 0.8)}
    spec = SweepSpec.random(axes, 64, seed=12345)
    assert traffic.random_points({k: list(v) for k, v in axes.items()},
                                 64, 12345) == list(spec.points)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_py_without_a_tpu_exits_nonzero_with_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_py_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


@pytest.mark.parametrize("name", CELLS)
def test_tiny_run_is_correct_and_reports_its_metrics(name):
    res = run_tiny(name)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = harness.Cell(BENCHMARK, name)
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "compared"
    assert res["compared"]["stat_mismatches"] == {"value": 0, "limit": 0}
    assert res["device"]["count"] == 1
    json.dumps(res)


@pytest.mark.parametrize("name,key,value", [
    ("memsys64-mixed-single", "l1_sets", 128),
    ("memsys64-mixed-single", "link_latency", 2.0),
    ("memsys64-mixed-single", "l1_buffer", 4),
    ("memsys64-mixed-single", "time_dtype", "float64"),
    ("onira-grid-sweep", "flush_cycles", 2.0),
    ("onira-grid-sweep", "mem_buffer", 2),
])
def test_system_refuses_a_size_the_program_does_not_build(name, key, value):
    cell = tiny_cell(name)
    system = harness.load_module("systems", cell.system)
    system.System(cell.config)
    with pytest.raises(harness.BenchError, match=key):
        system.System(dict(cell.config, **{key: value}))


def test_every_configuration_key_is_read():
    """Each number of a configuration file is a size the program's
    builder takes or is checked against."""
    for c in BENCHMARK["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        src = "".join(open(os.path.join(BENCH, d, cfg["system"] + ".py"))
                      .read() for d in ("systems", "reference"))
        for key, value in cfg.items():
            if isinstance(value, (int, float)) or key == "time_dtype":
                assert f'"{key}"' in src, (c["name"], key)
