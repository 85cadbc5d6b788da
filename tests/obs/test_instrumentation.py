"""The DSE stack on the bus: a telemetry-on sweep/search emits the
schema-v1 event catalogue, telemetry-off materializes zero events and
leaves results bit-identical, and a halving campaign over the grid
writes a readable JSONL log (the ISSUE acceptance path)."""
import numpy as np
import pytest

from repro.dse import (Objective, SuccessiveHalving, SweepSpec,
                       memoize_build, run_search, run_sweep)
from repro.obs import BUS, JsonlSink, capture, read_jsonl
from repro.sims.memsys import build

MAX_H = 2000.0


@pytest.fixture(scope="module")
def ctx():
    def build_fn():
        return build(n_cores=3, pattern="mixed", n_reqs=6, donate=True)

    bf = memoize_build(build_fn)
    sim, st = bf()
    total = int(np.sum(np.asarray(st.comp_state["core"]["remaining"])))

    def extract(sim, s):
        rem = int(np.sum(np.asarray(s.comp_state["core"]["remaining"])))
        vt = float(s.time)
        done = total - rem
        return {"virtual_time": vt, "remaining": rem,
                "est_finish": vt * total / max(done, 1)}

    pool = SweepSpec.grid({"conn_latency[-1]": [10., 20., 30., 40.],
                           "kind.l1.extra_hit_rate": [0.0, 0.4, 0.8]})
    return bf, extract, pool


def _rows_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra.keys() == rb.keys()
        for k in ra:
            if isinstance(ra[k], float):
                assert ra[k] == rb[k], k      # bit-identical, not approx
            else:
                assert ra[k] == rb[k], k


# ---------------------------------------------------------------------------
def test_sweep_emits_catalogue_and_stays_bit_identical(ctx):
    bf, extract, pool = ctx
    spec = SweepSpec.grid({"conn_latency[-1]": [10., 20.]})
    kw = dict(until=300.0, extract=extract, chunk=2)

    rows_off = run_sweep(bf, spec, **kw)
    seq0 = BUS.seq
    rows_off2 = run_sweep(bf, spec, **kw)
    assert BUS.seq == seq0            # disabled: zero events materialized

    with capture() as sink:
        rows_on = run_sweep(bf, spec, **kw)
    _rows_equal(rows_off, rows_on)    # telemetry never changes results
    _rows_equal(rows_off, rows_off2)

    kinds = set(sink.kinds())
    assert {"sweep.start", "sweep.group", "rounds.start", "round.end",
            "rounds.end", "transfer", "sweep.end"} <= kinds
    (start,) = sink.of("sweep.start")
    assert start["n_points"] == 2
    assert start["axes"]["axes"]["conn_latency[-1]"] == 2
    (end,) = sink.of("sweep.end")
    assert end["n_points"] == 2 and end["dur"] > 0.0
    # every round.end carries the live/pending/epoch accounting
    for ev in sink.of("round.end"):
        for key in ("round", "rung", "dur", "live", "epochs", "finished",
                    "survivors", "pending", "pool", "quantum"):
            assert key in ev, key
    (rend,) = sink.of("rounds.end")
    assert rend["B"] == 2
    # transfers: liveness pulls plus the final rows pull
    whats = {e["what"] for e in sink.of("transfer")}
    assert "rows" in whats
    # events are seq-ordered and schema-flat
    seqs = [e["seq"] for e in sink.events]
    assert seqs == sorted(seqs)


def test_metrics_registry_populated_by_sweep(ctx):
    bf, extract, pool = ctx
    spec = SweepSpec.grid({"conn_latency[-1]": [10., 20.]})
    BUS.metrics.clear()
    with capture():
        run_sweep(bf, spec, until=300.0, extract=extract, chunk=2)
        snap = BUS.metrics.snapshot()
    assert snap["dse.sweeps"] >= 1.0
    assert snap["dse.rounds"] >= 1.0
    assert snap["dse.round_s"]["count"] >= 1
    assert snap["dse.transfer.rows_s"]["count"] >= 1


# ---------------------------------------------------------------------------
def test_halving_search_emits_full_trace_and_jsonl(ctx, tmp_path):
    """The acceptance path: a halving search over the memsys grid with a
    JSONL sink produces a versioned event log covering ask/tell rounds,
    per-trial spend, and rung promotions."""
    bf, extract, pool = ctx
    path = tmp_path / "campaign.jsonl"
    sink = JsonlSink(str(path))
    BUS.attach(sink)
    try:
        with capture() as mem:
            drv = SuccessiveHalving(pool, "est_finish", max_horizon=MAX_H,
                                    min_horizon=60.0, eta=3, seed=0)
            res = run_search(bf, drv, extract=extract, chunk=4)
    finally:
        BUS.detach(sink)
        sink.close()

    assert res.best is not None

    kinds = set(mem.kinds())
    assert {"search.start", "search.ask", "trial", "search.tell",
            "rung.promote", "search.end"} <= kinds

    (start,) = mem.of("search.start")
    assert start["driver"] == "SuccessiveHalving"
    assert start["resumed_round"] == 0

    asks = mem.of("search.ask")
    tells = mem.of("search.tell")
    assert len(asks) == len(tells) == res.rounds
    assert [e["round"] for e in asks] == list(range(res.rounds))

    trials = mem.of("trial")
    assert len(trials) == len(res.rows)
    # round-0 trials are always cold and pay real cycles; promoted
    # configs that already finished may legitimately charge 0
    assert all(t["cycles"] > 0 for t in trials if t["round"] == 0)
    assert all(t["cycles"] >= 0 for t in trials)
    spend = sum(t["cycles"] for t in trials)
    assert spend == pytest.approx(res.budget, rel=1e-6)

    promos = mem.of("rung.promote")
    assert promos, "halving must report promotions"
    for ev in promos:
        assert ev["promoted"] + ev["dropped"] == ev["n"]
        if not ev["final"]:
            assert len(ev["promoted_points"]) == min(ev["promoted"], 8)

    (end,) = mem.of("search.end")
    assert end["trials"] == len(res.rows)
    assert end["budget"] == pytest.approx(res.budget)
    assert end["best"] == res.best

    # ... and the identical stream landed durably in the JSONL log
    logged = read_jsonl(str(path))
    assert [e["kind"] for e in logged] == mem.kinds()
    assert logged[-1]["kind"] == "search.end"


def test_warm_promotion_reports_cost_savings(ctx):
    """Warm halving's rung.promote events expose warm-vs-cold cost:
    spent (actual incremental charge) < replay_cycles (cold replay)."""
    bf, extract, pool = ctx
    with capture() as mem:
        drv = SuccessiveHalving(pool, "est_finish", max_horizon=MAX_H,
                                min_horizon=60.0, eta=3, seed=0, warm=True)
        run_search(bf, drv, extract=extract, chunk=4)
    later = [e for e in mem.of("rung.promote") if e["rung"] > 0]
    assert later
    for ev in later:
        assert ev["warm"] is True
        assert ev["spent"] is not None
        assert ev["spent"] < ev["replay_cycles"]


def test_search_disabled_is_silent_and_identical(ctx):
    bf, extract, pool = ctx

    def go():
        drv = SuccessiveHalving(pool, "est_finish", max_horizon=MAX_H,
                                min_horizon=60.0, eta=3, seed=0)
        return run_search(bf, drv, extract=extract, chunk=4)

    seq0 = BUS.seq
    r_off = go()
    assert BUS.seq == seq0
    with capture():
        r_on = go()
    assert r_off.best == r_on.best
    assert r_off.budget == r_on.budget
    _rows_equal(r_off.rows, r_on.rows)


# ---------------------------------------------------------------------------
SWEEP_SPANS = ("sweep", "sweep.build", "sweep.params", "sweep.rounds",
               "sweep.transfer", "sweep.extract")
ROUND_SPANS = ("round.assemble", "round.launch", "round.wait",
               "round.harvest", "rounds.final")


def _host_spans(logdir):
    """``(start, end, name)`` of the program's spans in the newest
    profiler trace under ``logdir``."""
    import glob
    import os
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = set(SWEEP_SPANS + ROUND_SPANS)
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in names]


def test_sweep_spans_reach_the_profiler_and_the_bus(ctx, tmp_path):
    """Every sweep and round phase is a profiler span under its plain
    name, nested as the code nests them, and a bus event while a sink
    is attached; with no sink the spans materialize no event."""
    import jax
    bf, extract, pool = ctx
    spec = SweepSpec.grid({"conn_latency[-1]": [10., 20., 30.]})
    kw = dict(until=300.0, extract=extract, chunk=2)
    run_sweep(bf, spec, **kw)                 # compile outside the trace
    seq0 = BUS.seq
    with jax.profiler.trace(str(tmp_path)):
        run_sweep(bf, spec, **kw)
    assert BUS.seq == seq0
    spans = _host_spans(str(tmp_path))
    assert {n for _, _, n in spans} == set(SWEEP_SPANS + ROUND_SPANS)
    (outer,) = [(s, e) for s, e, n in spans if n == "sweep"]
    (rounds,) = [(s, e) for s, e, n in spans if n == "sweep.rounds"]
    for s, e, n in spans:
        assert outer[0] <= s <= e <= outer[1], n
        if n in ROUND_SPANS:
            assert rounds[0] <= s <= e <= rounds[1], n
    # the phases of one level follow one another without overlapping
    for level in (SWEEP_SPANS[1:], ROUND_SPANS):
        iv = sorted((s, e) for s, e, n in spans if n in level)
        assert all(a[1] <= b[0] for a, b in zip(iv, iv[1:]))

    with capture() as sink:
        run_sweep(bf, spec, **kw)
    kinds = sink.kinds()
    for name in SWEEP_SPANS + ROUND_SPANS:
        assert name in kinds, name
        assert all(ev["dur"] >= 0.0 for ev in sink.of(name))
    assert kinds.count("sweep") == 1
    assert kinds.count("round.launch") == len(sink.of("round.end"))
