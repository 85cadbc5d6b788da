"""SweepSpec construction, static/traced splitting and param application."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dse import SweepSpec, apply_point, build_param_batch, stack_params
from repro.sims.memsys import build


@pytest.fixture(scope="module")
def sim():
    s, _ = build(n_cores=2, pattern="mixed", n_reqs=4, donate=False)
    return s


def test_grid_is_cartesian_product_in_order():
    spec = SweepSpec.grid({"a": [1, 2], "b": [10, 20, 30]})
    assert len(spec) == 6
    assert spec.points[0] == {"a": 1, "b": 10}
    assert spec.points[1] == {"a": 1, "b": 20}   # last axis fastest
    assert spec.points[-1] == {"a": 2, "b": 30}


def test_random_is_seeded_and_in_bounds():
    axes = {"u": (2.0, 8.0), "l": (1.0, 100.0, "log"), "c": [4, 8, 16, "x"]}
    s1 = SweepSpec.random(axes, n=32, seed=7)
    s2 = SweepSpec.random(axes, n=32, seed=7)
    assert s1.points == s2.points                # deterministic
    for p in s1:
        assert 2.0 <= p["u"] <= 8.0
        assert 1.0 <= p["l"] <= 100.0
        assert p["c"] in (4, 8, 16, "x")
    assert len({p["u"] for p in s1}) > 1         # actually samples


def test_random_per_axis_streams_survive_style_and_order_changes():
    """Axis substreams are keyed on (seed, name): the values axis "u"
    yields must be identical whether its neighbour is a choice list or a
    (lo, hi) range, and whatever the dict order or axis count."""
    u_alone = [p["u"] for p in SweepSpec.random({"u": (2.0, 8.0)}, 16,
                                                seed=3)]
    with_choice = SweepSpec.random({"u": (2.0, 8.0), "c": [4, 8, 16]},
                                   16, seed=3)
    with_range = SweepSpec.random({"c": (0.0, 1.0), "u": (2.0, 8.0)},
                                  16, seed=3)
    assert [p["u"] for p in with_choice] == u_alone
    assert [p["u"] for p in with_range] == u_alone
    assert [p["c"] for p in with_choice] != [p["c"] for p in with_range]


def test_random_int_axes_come_back_as_python_ints():
    import numpy as np
    spec = SweepSpec.random({"r": (2, 8),                  # int range
                             "c": [1, 2, 4, 8],            # int choice
                             "n": [np.int32(3), np.int32(5), np.int32(9)],
                             "f": (2.0, 8.0)}, 32, seed=11)
    for p in spec:
        assert type(p["r"]) is int and 2 <= p["r"] <= 8    # inclusive
        assert type(p["c"]) is int and p["c"] in (1, 2, 4, 8)
        assert type(p["n"]) is int and p["n"] in (3, 5, 9)
        assert type(p["f"]) is float
    assert {p["r"] for p in spec} == set(range(2, 9))      # hits both ends
    # same-seed determinism holds for every style
    assert spec.points == SweepSpec.random(
        {"r": (2, 8), "c": [1, 2, 4, 8],
         "n": [np.int32(3), np.int32(5), np.int32(9)],
         "f": (2.0, 8.0)}, 32, seed=11).points


def test_explicit_rejects_ragged_points_naming_index_and_keys():
    with pytest.raises(ValueError) as e:
        SweepSpec.explicit([{"a": 1.0, "b": 2.0},
                            {"a": 1.0, "b": 2.0},
                            {"a": 3.0, "c": 4.0}])
    msg = str(e.value)
    assert "point 2" in msg                 # the offending index
    assert "'b'" in msg and "'c'" in msg    # missing and extra keys
    # uniform points construct fine
    SweepSpec.explicit([{"a": 1.0}, {"a": 2.0}])
    # different static groups stack separately, so their traced axes
    # may legitimately differ — no ragged=True needed
    spec = SweepSpec.explicit([{"static.x": 1, "a": 1.0},
                               {"static.x": 2, "b": 2.0}])
    assert len(spec) == 2
    # ...but raggedness *within* one static group still raises
    with pytest.raises(ValueError, match="point 1"):
        SweepSpec.explicit([{"static.x": 1, "a": 1.0},
                            {"static.x": 1, "b": 2.0}])
    # and ragged=True skips the check entirely
    SweepSpec.explicit([{"static.x": 1, "a": 1.0},
                        {"static.x": 1, "b": 2.0}], ragged=True)


def test_split_static_groups_and_preserves_indices():
    spec = SweepSpec.grid({"static.super_epoch": [1, 4],
                           "conn_latency": [5.0, 9.0]})
    groups = spec.split_static()
    assert len(groups) == 2
    (st1, ix1, tr1), (st2, ix2, tr2) = groups
    assert st1 == {"super_epoch": 1} and st2 == {"super_epoch": 4}
    assert ix1 == [0, 1] and ix2 == [2, 3]
    assert tr1 == [{"conn_latency": 5.0}, {"conn_latency": 9.0}] == tr2


def test_apply_point_paths(sim):
    base = sim.default_params()
    p = apply_point(base, {"conn_latency": 7.0})
    assert np.all(np.asarray(p.conn_latency) == 7.0)
    p = apply_point(base, {"conn_latency[-1]": 50.0})
    np.testing.assert_array_equal(
        np.asarray(p.conn_latency[:-1]), np.asarray(base.conn_latency[:-1]))
    assert float(p.conn_latency[-1]) == 50.0
    p = apply_point(base, {"period.dram": 4.0, "period.core[0]": 2.0})
    assert float(p.periods["dram"][0]) == 4.0
    assert float(p.periods["core"][0]) == 2.0
    assert float(p.periods["core"][1]) == 1.0
    p = apply_point(base, {"kind.l1.extra_hit_rate": 0.5})
    assert float(p.kind["l1"]["extra_hit_rate"]) == 0.5
    # base is never mutated
    assert float(base.kind["l1"]["extra_hit_rate"]) == 0.0
    assert np.all(np.asarray(base.periods["dram"]) == 1.0)


@pytest.mark.parametrize("bad", [
    {"nope": 1.0},
    {"period.nokind": 1.0},
    {"kind.l1.nope": 1.0},
    {"kind.nokind.x": 1.0},
    {"static.super_epoch": 2},       # static must not reach apply_point
])
def test_apply_point_rejects_unknown_paths(sim, bad):
    with pytest.raises(KeyError):
        apply_point(sim.default_params(), bad)


def test_apply_point_rejects_out_of_range_index(sim):
    with pytest.raises(AssertionError):
        apply_point(sim.default_params(), {"conn_latency[99]": 2.0})


# ---------------------------------------------------------------------------
# eager axis-path validation (no deep KeyError mid-run_sweep)
# ---------------------------------------------------------------------------
def test_validate_names_bad_path_and_valid_axes(sim):
    spec = SweepSpec.grid({"period.l1x": [1.0, 2.0]})
    with pytest.raises(ValueError) as e:
        spec.validate(sim)
    msg = str(e.value)
    assert "period.l1x" in msg          # the bad path, by name
    assert "period.l1" in msg           # ...and the valid alternatives
    assert "kind.l1.extra_hit_rate" in msg


def test_validate_at_construction_via_validate_for(sim):
    with pytest.raises(ValueError, match="nope"):
        SweepSpec.grid({"nope": [1.0]}, validate_for=sim)
    with pytest.raises(ValueError, match="kind.l1.no_leaf"):
        SweepSpec.explicit([{"kind.l1.no_leaf": 0.5}], validate_for=sim)
    with pytest.raises(ValueError, match="out of range"):
        SweepSpec.random({"conn_latency[99]": (1.0, 2.0)}, n=2,
                         validate_for=sim)


def test_validate_accepts_every_documented_axis_form(sim):
    spec = SweepSpec.explicit([{
        "conn_latency": 2.0, "conn_latency[-1]": 10.0,
        "period.dram": 2.0, "period.core[0]": 2.0,
        "kind.l1.extra_hit_rate": 0.5,
        "static.super_epoch": 2}])
    assert spec.validate(sim) is spec                   # chains
    # static axes are checked only against an explicit whitelist
    spec.validate(sim, static_ok=["super_epoch"])
    with pytest.raises(ValueError, match="super_epoch"):
        spec.validate(sim, static_ok=["other_kwarg"])


def test_run_sweep_raises_eagerly_on_unknown_traced_axis(sim):
    from repro.dse import run_sweep
    from repro.sims.memsys import build
    spec = SweepSpec.grid({"period.l1x": [1.0, 2.0]})
    with pytest.raises(ValueError, match="period.l1x"):
        run_sweep(lambda: build(n_cores=2, pattern="mixed", n_reqs=4),
                  spec, until=100.0)


def test_run_sweep_rejects_unknown_static_kwarg_before_building():
    from repro.dse import run_sweep
    builds = []

    def build_fn(super_epoch=None):
        builds.append(super_epoch)
        raise AssertionError("must not build")

    spec = SweepSpec.grid({"static.super_epok": [1]})
    with pytest.raises(ValueError, match="super_epok"):
        run_sweep(build_fn, spec, until=100.0)
    assert builds == []


def test_run_sweep_validates_each_static_group_against_its_own_build():
    """Axis paths are checked per compile group: an index that is only
    valid for the larger topology must not fail against the smaller
    group's sim (and vice versa must still be caught)."""
    from repro.dse import run_sweep
    from repro.sims.memsys import build

    def build_fn(n_cores):
        return build(n_cores=n_cores, pattern="mixed", n_reqs=4,
                     donate=False)

    # n_cores=2 -> 3 connections, n_cores=3 -> 4: conn_latency[3] only
    # exists in the second group
    spec = SweepSpec.explicit([
        {"static.n_cores": 2, "conn_latency[-1]": 10.0},
        {"static.n_cores": 3, "conn_latency[3]": 10.0}])
    rows = run_sweep(build_fn, spec, until=20000.0)
    assert len(rows) == 2 and all(r["epochs"] > 0 for r in rows)

    bad = SweepSpec.explicit([{"static.n_cores": 2, "conn_latency[3]": 1.0}])
    with pytest.raises(ValueError, match="out of range"):
        run_sweep(build_fn, bad, until=100.0)


def test_split_shape_strips_prefix():
    from repro.dse import split_shape
    shape, traced = split_shape({"shape.core": 4, "conn_latency": 5.0})
    assert shape == {"core": 4}
    assert traced == {"conn_latency": 5.0}


def test_apply_point_rejects_shape_axes(sim):
    with pytest.raises(KeyError, match="TopologyFamily"):
        apply_point(sim.default_params(), {"shape.core": 2})


def test_stack_params_shapes(sim):
    spec = SweepSpec.grid({"conn_latency[-1]": [10.0, 20.0, 40.0]})
    pb = build_param_batch(sim, list(spec))
    assert pb.conn_latency.shape == (3,) + sim.default_params().conn_latency.shape
    assert pb.periods["core"].shape[0] == 3
    np.testing.assert_array_equal(
        np.asarray(pb.conn_latency[:, -1]), [10.0, 20.0, 40.0])
    # non-swept leaves are identical across the batch
    assert np.all(np.asarray(pb.kind["l1"]["extra_hit_rate"]) == 0.0)


# ---------------------------------------------------------------------------
# build_param_batch: host assembly, one transfer, same batch as the eager
# composition of apply_point and stack_params
# ---------------------------------------------------------------------------
class _HandParams:
    """A target whose defaults hold what no shipped model does: int32
    leaves, weakly typed ones and a nested kind-param tree."""

    def default_params(self):
        from repro.core import SimParams
        return SimParams(
            conn_latency=jnp.asarray(np.arange(1.0, 4.0, dtype=np.float32)),
            periods={"a": jnp.ones(2, jnp.int32)},
            kind={"a": {"n": jnp.asarray(3),             # weak int32
                        "w": jnp.asarray(1.0)},          # weak float32
                  "b": {"x": {"y": jnp.asarray(0.5)}},   # weak, nested
                  "c": {}})


def _target(name):
    if name == "memsys":
        return build(n_cores=2, pattern="mixed", n_reqs=4, donate=False)[0]
    if name == "onira":
        from repro.sims import onira
        progs = [onira.MICROBENCHES[k]() for k in onira.MICROBENCHES]
        return onira.build_onira(progs)[0]
    return _HandParams()


def _every_form(sim, b):
    """``b`` points that cover every traced path form: whole leaf,
    indexed from either end, ``period.<k>`` whole and indexed,
    ``kind.<k>.<leaf>``, a fill then an indexed override in one point,
    an override then a fill, empty points, and int, np.int64 and float
    values.  Point 0 holds every form at once."""
    from repro.dse import valid_axes
    params = sim.default_params()
    pk = sorted(params.periods)[0]
    kleaf = next(a for a in valid_axes(params) if a.startswith("kind."))
    forms = [
        lambda r: {"conn_latency": 4, "conn_latency[-1]": 1.5 + r,
                   f"period.{pk}": 16777217.0, f"period.{pk}[0]": 3.25,
                   kleaf: 2.7, "conn_latency[0]": np.int64(6)},
        lambda r: {},
        lambda r: {"conn_latency": 1 + r % 9},
        lambda r: {"conn_latency[0]": np.int64(2 + r % 4)},
        lambda r: {"conn_latency[-1]": 1.5 + 0.1 * (r % 11)},
        lambda r: {f"period.{pk}": 1.0 + 0.3 * (r % 3)},
        lambda r: {f"period.{pk}": np.int64(2 + r % 3)},
        lambda r: {f"period.{pk}[-1]": 2 + r % 2},
        lambda r: {kleaf: 0.1 * (r % 9)},
        lambda r: {kleaf: r % 4},
        lambda r: {kleaf: np.int64(r % 5)},
        lambda r: {"conn_latency[0]": 9.0, "conn_latency": 0.7 + r},
    ]
    return [forms[r % len(forms)](r) for r in range(b)]


@pytest.mark.parametrize("b", [1, 257])
@pytest.mark.parametrize("target", ["memsys", "onira", "hand"])
def test_build_param_batch_equals_eager_composition(target, b):
    sim = _target(target)
    pts = _every_form(sim, b)
    got = build_param_batch(sim, pts)
    base = sim.default_params()
    want = stack_params([apply_point(base, p) for p in pts])
    got_l, got_def = jax.tree_util.tree_flatten_with_path(got)
    want_l, want_def = jax.tree_util.tree_flatten_with_path(want)
    assert got_def == want_def
    for (path, g), (_, w) in zip(got_l, want_l):
        where = jax.tree_util.keystr(path)
        assert isinstance(g, jax.Array), where
        assert (g.shape, g.dtype, g.weak_type) == \
            (w.shape, w.dtype, w.weak_type), where
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), where
    if target == "hand":        # the flags the comparison must carry
        assert got.kind["a"]["w"].weak_type
        assert not got.kind["a"]["n"].weak_type         # retyped by writes
        assert got.kind["a"]["n"].dtype == jnp.int32
        assert got.periods["a"].dtype == jnp.int32


@pytest.mark.parametrize("bad", [
    {"nope": 1.0},
    {"period.nokind": 1.0},
    {"kind.l1.nope": 1.0},
    {"kind.nokind.x": 1.0},
    {"static.super_epoch": 2},
    {"shape.core": 2},
    {"conn_latency[99]": 2.0},
    {"kind.l1.extra_hit_rate[0]": 0.5},     # kind params take no index
])
def test_build_param_batch_raises_what_apply_point_raises(sim, bad):
    from repro.obs import capture
    with pytest.raises((KeyError, AssertionError)) as one:
        apply_point(sim.default_params(), bad)
    pts = [{"conn_latency": 2.0}, {"conn_latency[-1]": 3.0}, bad,
           {"kind.l1.extra_hit_rate": 0.5}]
    with capture() as sink:
        with pytest.raises(type(one.value)) as batch:
            build_param_batch(sim, pts)
    assert type(batch.value) is type(one.value)
    assert batch.value.args == one.value.args
    assert sink.of("transfer") == []        # checked before any transfer


def test_run_sweep_sends_params_once_per_group_and_never_retraces():
    from repro.dse import memoize_build, run_sweep, runner_for
    from repro.obs import BUS, capture

    bf = memoize_build(lambda n_cores: build(
        n_cores=n_cores, pattern="mixed", n_reqs=4, donate=False))
    spec = SweepSpec.explicit(
        [{"static.n_cores": 2, "conn_latency[-1]": float(v)}
         for v in (5, 10, 20)]
        + [{"static.n_cores": 3, "conn_latency[-1]": float(v)}
           for v in (5, 10)])
    kw = dict(until=400.0, chunk=4)
    rows = run_sweep(bf, spec, **kw)                    # warm
    sims = {n: bf(n_cores=n)[0] for n in (2, 3)}
    tc0 = {n: runner_for(s).trace_count for n, s in sims.items()}
    seq0 = BUS.seq
    again = run_sweep(bf, spec, **kw)
    assert BUS.seq == seq0                  # no sink: no event
    assert {n: runner_for(s).trace_count for n, s in sims.items()} == tc0
    assert again == rows

    with capture() as sink:
        run_sweep(bf, spec, **kw)
    params = [e for e in sink.of("transfer") if e["what"] == "params"]
    assert [e["lanes"] for e in params] == [3, 2]
    for e, n in zip(params, (2, 3)):
        leaves = jax.tree.leaves(sims[n].default_params())
        assert e["bytes"] == e["lanes"] * sum(x.nbytes for x in leaves)
        assert e["dur"] >= 0.0
    assert {n: runner_for(s).trace_count for n, s in sims.items()} == tc0
