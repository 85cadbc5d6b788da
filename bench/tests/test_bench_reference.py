"""The plain reference agrees with the program bit for bit at small
sizes, and the control (the reference with its time in bfloat16) does
not: the comparison that decides ``correct`` separates the two."""
import jax
import numpy as np
import pytest

from bench_tiny import SEED, tiny_cell
from benchlib import compare, harness, traffic
from benchlib.refengine import Num

MEMSYS_POINTS = [
    {},
    {"conn_latency[-1]": 10.0 + 30.0 * 37 / 255,
     "kind.l1.extra_hit_rate": 0.8 * 131 / 255},
    {"conn_latency[-1]": 40.0, "kind.l1.extra_hit_rate": 0.8},
]
ONIRA_POINTS = [{"conn_latency": 1, "kind.cpu.flush_cycles": 1},
                {"conn_latency": 37, "kind.cpu.flush_cycles": 6},
                {"conn_latency": 100, "kind.cpu.flush_cycles": 8}]


def _program(system, params_point, state, until):
    """One run of the program's own simulation at ``params_point``, on
    top of the parameters the configuration sets."""
    from repro.dse import apply_point
    sim = system.sim
    base = getattr(system, "params", None) or sim.default_params()
    out = sim.run(state, until=until,
                  params=apply_point(base, params_point))
    if hasattr(system, "extract"):
        return system.extract(sim, jax.device_get(out))
    return system.stats(out)


@pytest.mark.parametrize("point", MEMSYS_POINTS)
@pytest.mark.parametrize("until", [400.0, 1e7])
def test_memsys_reference_equals_program(point, until):
    cell = tiny_cell("memsys64-mixed-single")
    system = harness.load_module("systems", "memsys").System(cell.config)
    ref_mod = harness.load_module("reference", "memsys")
    for k in range(2):
        inputs = ref_mod.inputs(cell.config, traffic.job_rng(SEED, k))
        got = _program(system, point, system.state(inputs), until)
        ref = ref_mod.simulate(cell.config, inputs, point, until)
        assert compare.mismatches(got, ref) == []
        assert ref["epochs"] > 0


def test_memsys_single_path_equals_reference():
    cell = tiny_cell("memsys64-mixed-single")
    system = harness.load_module("systems", "memsys").System(cell.config)
    ref_mod = harness.load_module("reference", "memsys")
    inputs = ref_mod.inputs(cell.config, traffic.job_rng(SEED, 0))
    got = system.stats(system.run(system.state(inputs), 1e7))
    ref = ref_mod.simulate(cell.config, inputs, {}, 1e7)
    assert compare.mismatches(got, ref) == []
    # every read is served, by a hit in its L1 or by the DRAM port
    assert ref["remaining"] == 0 and ref["outstanding"] == 0
    assert ref["hits"] + ref["reads_done"] == 4 * 12 and ref["hits"] > 0


@pytest.mark.parametrize("point", ONIRA_POINTS)
def test_onira_reference_equals_program(point):
    cell = tiny_cell("onira-grid-sweep")
    system = harness.load_module("systems", "onira").System(cell.config)
    ref_mod = harness.load_module("reference", "onira")
    inputs = ref_mod.inputs(cell.config, traffic.job_rng(SEED, 0))
    got = _program(system, point, system.sim.copy_state(system.template),
                   60000.0)
    ref = ref_mod.simulate(cell.config, inputs, point, 60000.0)
    assert compare.mismatches(got, ref) == []
    assert all(ref["done"])


@pytest.mark.parametrize("name", ["memsys64-mixed-single",
                                  "onira-grid-sweep"])
def test_control_fails_the_comparison(name):
    cell = tiny_cell(name)
    ref_mod = harness.load_module("reference", cell.system)
    pts = MEMSYS_POINTS if cell.system == "memsys" else ONIRA_POINTS
    pairs = []
    for k, point in enumerate(pts):
        inputs = ref_mod.inputs(cell.config, traffic.job_rng(SEED, k))
        ref = ref_mod.simulate(cell.config, inputs, point, 1e5)
        ctl = compare.control(ref_mod.simulate, cell.config, inputs, point,
                              1e5, ref)
        pairs.append((str(point), ctl, ref))
    verdict = compare.judge(pairs)
    assert verdict["correct"] is False
    assert verdict["numbers"]["stat_mismatches"][0] >= len(pts)


def test_time_rounding_of_each_precision():
    f32, bf16 = Num("float32"), Num("bfloat16")
    assert f32.f(135941.0) == 135941.0 and bf16.f(135941.0) != 135941.0
    assert f32.after(3.0, 1.0) == 4.0 and f32.at_or_after(3.2, 1.0) == 4.0
    assert f32.at_or_after(3.0, 1.0) == 3.0
    assert np.float32(f32.eps) == np.float32(1e-3)
