"""Sharded sweep rounds over the device mesh (DSE.md "Sharded sweeps
and the persistent cache").

The hard bar: ``shard=True`` must be a pure *placement* decision — every
row of ``run_batch`` / ``run_rounds`` / ``run_sweep`` / ``run_search``
bit-identical to the single-device vmap path, on every memsys pattern,
on masked family lanes and on mixed-horizon batches.  Multi-device
behavior (the mesh itself, non-divisible-batch padding, global
rebalancing) is only reachable with >1 device, so those tests run in a
subprocess with forced host devices, like the ``test_runner.py`` one.

Single-device properties (the `shard` argument's normalization, the
per-topology autotune slot, mesh-aligned ladders) are tested inline.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.dse import BatchRunner, build_param_batch, stack_states
from repro.dse.runner import _align_up, _shard_devices
from repro.sims.memsys import build

_TWO_DEV_ENV = dict(
    XLA_FLAGS="--xla_force_host_platform_device_count=2")


def _run_two_device(script: str, timeout: int = 900):
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.update(_TWO_DEV_ENV)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-4000:])
    return r.stdout


# ---------------------------------------------------------------------------
# inline (single-device) contracts
# ---------------------------------------------------------------------------
def test_shard_devices_normalization():
    n = jax.local_device_count()
    assert _shard_devices(False) == 1
    assert _shard_devices(0) == 1
    assert _shard_devices(None) == 1
    assert _shard_devices(True) == n
    assert _shard_devices(1) == 1
    assert _shard_devices(999) == n          # clamped to the host
    assert _align_up(65, 2) == 66 and _align_up(64, 2) == 64
    assert _align_up(5, 1) == 5


def test_tuned_top_keyed_on_device_count_not_shard_flag():
    """shard=False and shard=1 are the same topology (one device) and
    must share the autotuned rung slot; a different mesh width gets its
    own slot — a runner reused under a different device count must not
    inherit a stale chunk rung."""
    sim, st = build(n_cores=2, pattern="mixed", n_reqs=6, donate=False)
    r = BatchRunner(sim)
    r._tuned_top[1] = 8          # pretend a 1-device autotune ran
    B = 16
    pb = build_param_batch(
        sim, [{"conn_latency[-1]": float(10 + i)} for i in range(B)])
    r.run_rounds(st, pb, 2000.0, shard=False)
    assert r.last_rounds["chunk"] == 8       # consumed the d=1 slot
    r.run_rounds(st, pb, 2000.0, shard=1)
    assert r.last_rounds["chunk"] == 8       # same slot, no re-probe
    assert set(r._tuned_top) == {1}          # nothing keyed on bools
    assert all(isinstance(k, int) for k in r._tuned_top)


def test_single_device_shard_rows_identical():
    """With one device, shard=True routes through the same plain-vmap
    executable — byte-identical results and a shared executable cache."""
    sim, st = build(n_cores=2, pattern="mixed", n_reqs=6, donate=False)
    r = BatchRunner(sim)
    pb = build_param_batch(
        sim, [{"conn_latency[-1]": float(v)} for v in (10, 20, 30)])
    a = r.run_batch(stack_states(st, 3), pb, 20000.0, shard=False)
    n_fns = len(r._fns)
    b = r.run_batch(stack_states(st, 3), pb, 20000.0,
                    shard=jax.local_device_count())
    if jax.local_device_count() == 1:
        assert len(r._fns) == n_fns          # same (3, 1) executable
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# the 2-device mesh: bit-identity across every layer + padding
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_sharded_rounds_sweep_search_bit_identical_two_devices():
    """One subprocess, four layers: (1) ``run_rounds`` on a B=65
    mixed-horizon batch — bit-identical to monolithic ``run_batch`` and
    padded to 66 so *both* devices run 33 lanes (no largest-divisor
    fallback); (2) ``run_sweep`` over all five memsys patterns as
    static groups with mixed horizons; (3) masked family lanes
    (``shape.core``); (4) a seeded halving ``run_search`` — all rows
    bit-identical between shard=True and the vmap path."""
    out = _run_two_device("""
        import jax, numpy as np
        assert jax.local_device_count() == 2
        from repro.dse import (BatchRunner, Objective, SuccessiveHalving,
                               SweepSpec, build_param_batch, run_search,
                               run_sweep, stack_states)
        from repro.sims.memsys import build, build_family

        # ---- 1. rounds, B=65 (odd: padding must engage), ~8x spread
        sim, st = build(n_cores=2, pattern="mixed", n_reqs=6,
                        donate=False)
        B = 65
        pts = [{"conn_latency[-1]": float(10 + (i % 7) * 5)}
               for i in range(B)]
        pb = build_param_batch(sim, pts)
        u = np.linspace(400.0, 3200.0, B).astype(np.float32)
        r = BatchRunner(sim)
        ref = r.run_batch(stack_states(st, B), pb, u)
        out = r.run_rounds(st, pb, u, shard=True)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert r.last_shard == 2 and r.last_rounds["shard"] == 2
        # every sharded executable spans d=2 with an even batch: B=65
        # ran padded to 66, not shrunk to a divisor (65 is odd -> the
        # old pmap path would have collapsed to d=1)
        sharded = [k for k in r._fns
                   if isinstance(k[0], int) and k[1] == 2]
        assert sharded and all(k[0] % 2 == 0 for k in sharded), sharded
        mono = r.run_batch(stack_states(st, B), pb, u, shard=True)
        assert (66, 2) in r._fns and (65, 2) not in r._fns
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(mono)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ROUNDS_OK")

        # ---- 2. run_sweep: all five patterns x mixed horizons
        spec = SweepSpec.grid({
            "static.pattern": ["compute", "stream", "pointer",
                               "idle_half", "mixed"],
            "conn_latency[-1]": [10.0, 25.0],
            "kind.core.think_scale": [1.0, 1.5]})
        def bf(pattern="mixed"):
            return build(n_cores=2, pattern=pattern, n_reqs=6,
                         donate=False)
        u2 = np.linspace(500.0, 4000.0, len(spec)).astype(np.float32)
        assert run_sweep(bf, spec, until=u2) == \\
            run_sweep(bf, spec, until=u2, shard=True)
        print("SWEEP_OK")

        # ---- 3. masked family lanes (shape.core)
        fspec = SweepSpec.grid({"shape.core": [1, 2],
                                "kind.core.think_scale": [1.0, 1.4]})
        def fb(shape=None):
            return build_family(shape=shape, n_cores=2, pattern="mixed",
                                n_reqs=6, donate=False)
        fu = np.linspace(600.0, 2400.0, len(fspec)).astype(np.float32)
        assert run_sweep(fb, fspec, until=fu) == \\
            run_sweep(fb, fspec, until=fu, shard=True)
        print("FAMILY_OK")

        # ---- 4. search: same seeded trajectory under the mesh
        def search(shard):
            pool = SweepSpec.grid({
                "conn_latency[-1]": [10.0, 20.0, 30.0, 40.0],
                "kind.core.think_scale": [1.0, 1.5]})
            drv = SuccessiveHalving(
                pool, Objective("virtual_time"), max_horizon=2000.0,
                min_horizon=500.0, eta=2, seed=7)
            def bsearch():
                return build(n_cores=2, pattern="mixed", n_reqs=6,
                             donate=False)
            return run_search(bsearch, drv, shard=shard)
        a, b = search(False), search(True)
        assert a.rows == b.rows and a.best == b.best
        print("SEARCH_OK")
    """)
    for tag in ("ROUNDS_OK", "SWEEP_OK", "FAMILY_OK", "SEARCH_OK"):
        assert tag in out, out


@pytest.mark.slow
def test_sharded_rebalance_telemetry_two_devices():
    """Under the mesh, survivors re-pack globally each round; the
    ``shard.rebalance`` events must report the lanes that changed shard
    (and the rounds must still be bit-identical — covered above)."""
    out = _run_two_device("""
        import jax, numpy as np
        assert jax.local_device_count() == 2
        from repro.dse import BatchRunner, ChunkSchedule, \\
            build_param_batch
        from repro.obs.bus import capture
        from repro.sims.memsys import build
        sim, st = build(n_cores=2, pattern="mixed", n_reqs=48,
                        donate=False)
        B = 32
        pb = build_param_batch(
            sim, [{"conn_latency[-1]": float(10 + i)} for i in range(B)])
        # adversarial horizons: even lanes finish early, odd lanes run
        # long -- survivors compact into fresh shard layouts over many
        # small-quantum rounds, so some must land on the other shard
        u = np.where(np.arange(B) % 2 == 0, 300.0, 6000.0) \\
            .astype(np.float32)
        sched = ChunkSchedule(ladder=(16, 8), quantum=32,
                              min_round_s=0.0)
        with capture() as sink:
            BatchRunner(sim).run_rounds(st, pb, u, schedule=sched,
                                        shard=True)
        ev = [e for e in sink.events if e["kind"] == "shard.rebalance"]
        assert ev, "no shard.rebalance events under a 2-device mesh"
        assert all(e["shards"] == 2 for e in ev)
        assert sum(e["moved"] for e in ev) > 0, ev
        assert all(0 <= e["moved"] <= e["lanes"] for e in ev)
        rs = [e for e in sink.events if e["kind"] == "rounds.start"]
        assert rs and rs[0]["shard"] == 2
        # mesh-aligned ladder: every rung is even
        assert all(r % 2 == 0 for r in rs[0]["ladder"]), rs[0]
        print("REBALANCE_OK")
    """)
    assert "REBALANCE_OK" in out


@pytest.mark.slow
def test_sharded_sweep_with_campaign_cache_two_devices(tmp_path):
    """With a campaign cache configured, sharded rounds run the same
    rows as the unsharded sweep: compaction hands a sharded rung
    replicated gathers, which an AOT executable compiled for another
    input layout refuses — so only single-device rungs persist whole."""
    out = _run_two_device(f"""
        import numpy as np
        from repro.dse import SweepSpec, cache, run_sweep
        from repro.sims.memsys import build
        cache.configure({str(tmp_path)!r})
        B = 32
        spec = SweepSpec.explicit(
            [{{"conn_latency[-1]": 10.0 + i,
               "kind.l1.extra_hit_rate": 0.8 * ((i * 7) % B) / (B - 1)}}
             for i in range(B)])
        u = (250.0 + 1750.0 * ((np.arange(B) * 11) % B) / (B - 1)) \\
            .astype(np.float32)
        def bf():
            return build(n_cores=4, n_reqs=32)
        sharded = run_sweep(bf, spec, until=u, shard=True)
        assert sharded == run_sweep(bf, spec, until=u)
        assert cache.stats()["write_failures"] == 0, cache.stats()
        print("CACHED_SHARDED_OK")
    """)
    assert "CACHED_SHARDED_OK" in out
