"""Sharded sweep rounds over the local device mesh versus the monolithic
pmap round it replaces (DSE.md "Sharded sweeps and the persistent cache").

The multi-device path needs at least two devices, and this module runs
in the process that holds them (one process per chip): on a multi-chip
host it spans the chips; on the CPU run it with forced host devices,
``XLA_FLAGS=--xla_force_host_platform_device_count=2``.  With fewer than
two devices it fails instead of measuring something else.  On the
straggler-heavy workload of ``dse_throughput`` (B=256, per-lane horizons
spread ~8x):

* ``pmap_monolith`` — lanes laid out ``[D, 256/D]``, ONE ``jax.pmap`` of
  the vmapped run to every lane's own horizon: the pre-rounds sharding
  story.  Each device iterates until its *slowest* local lane is done,
  finished lanes burn masked epochs, and a drained device idles while
  its neighbour's stragglers grind on.
* ``sharded_rounds`` — ``run_rounds(shard=True)``: one shard_map-of-vmap
  executable per ladder rung across the whole mesh, with the global
  harvest/compact/refill re-packing survivors across shards each round.

Both paths compute bit-identical rows (asserted here); the CI bar gates
the speedup at >= 1.5x.
"""
import time

B = 256
SPREAD = 8       # horizons span [drain/8, ~drain]
REPS = 2


def _measure():
    import jax
    import numpy as np
    from repro.dse import BatchRunner, build_param_batch, stack_states
    from repro.sims.memsys import build

    D = jax.local_device_count()
    if D < 2 or B % D:
        raise RuntimeError(
            f"sharded_sweep needs 2+ devices dividing {B}, found {D} "
            f"{jax.devices()[0].platform} device(s)")

    sim, st = build(n_cores=8, pattern="mixed", n_reqs=256, donate=True)
    pts = [{"conn_latency[-1]": 10.0 + (30.0 * i) / (B - 1),
            "kind.l1.extra_hit_rate": 0.8 * ((i * 7) % B) / (B - 1)}
           for i in range(B)]
    pb = build_param_batch(sim, pts)

    # per-lane horizons: ~8x spread with a straggler skew — 90% of the
    # lanes stop in the low-horizon band, 10% climb to the workload's
    # drain time (the classic DSE shape: most configs answer quickly, a
    # few pathological ones grind).  An i*11 stride decorrelates the
    # stragglers from the param axes AND from the [D, B/D] shard
    # boundary, so the pmap baseline is not rigged: every device gets
    # its fair share of long lanes.
    r0 = BatchRunner(sim)
    probe = r0.run_batch(stack_states(st, 1),
                         jax.tree.map(lambda x: x[:1], pb), 1e9)
    top = float(probe.time[0]) * 0.9
    lo = top / SPREAD
    frac = ((np.arange(B) * 11) % B) / (B - 1)
    u = np.where(frac < 0.9, lo + (frac / 0.9) * lo * 0.25,
                 lo + (top - lo) * (frac - 0.9) / 0.1).astype(np.float32)
    m = np.full(B, 2_000_000, np.int32)

    # ---- baseline: one pmapped round, [D, B/D] lanes, full horizons
    def one(s, p, uu, mm):
        return sim._run(s, uu, mm, params=p)
    pm = jax.pmap(jax.vmap(one), donate_argnums=(0,))
    mesh = lambda t: jax.tree.map(
        lambda x: x.reshape((D, B // D) + x.shape[1:]), t)
    pbs = mesh(pb)
    us, ms = u.reshape(D, B // D), m.reshape(D, B // D)
    base_out = pm(mesh(stack_states(st, B)), pbs, us, ms)  # compile
    jax.block_until_ready(base_out.time)
    dt_base = float("inf")
    for _ in range(REPS):
        sb = jax.block_until_ready(mesh(stack_states(st, B)))
        t0 = time.perf_counter()
        base_out = pm(sb, pbs, us, ms)
        base_out.time.block_until_ready()
        dt_base = min(dt_base, time.perf_counter() - t0)

    # ---- sharded rounds: ladder + global cross-shard rebalancing
    runner = BatchRunner(sim)
    out = runner.run_rounds(st, pb, u, shard=True)   # compile + autotune
    out.time.block_until_ready()
    np.testing.assert_array_equal(                   # same computation
        np.asarray(out.time), np.asarray(base_out.time).reshape(B))
    out = runner.run_rounds(st, pb, u, shard=True)   # narrowed-ladder warm
    out.time.block_until_ready()
    dt_rounds = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = runner.run_rounds(st, pb, u, shard=True)
        out.time.block_until_ready()
        dt_rounds = min(dt_rounds, time.perf_counter() - t0)
    return {"dt_base": dt_base, "dt_rounds": dt_rounds, "D": D,
            "chunk": runner.last_rounds["chunk"],
            "rounds": runner.last_rounds["rounds"],
            "shard": runner.last_rounds["shard"]}


def bench():
    d = _measure()
    b, base_cps = B, B / d["dt_base"]
    cps = b / d["dt_rounds"]
    return [
        {
            "name": "sharded_sweep/pmap_monolith_B256_mixed",
            "us_per_call": d["dt_base"] * 1e6,
            "derived": f"{base_cps:.1f} configs/s (one pmap round, "
                       f"[{d['D']}, {b // d['D']}] lanes, ~8x horizon "
                       f"spread)",
            "configs_per_sec": base_cps,
        },
        {
            "name": "sharded_sweep/sharded_rounds_B256_mixed",
            "us_per_call": d["dt_rounds"] * 1e6,
            "derived": f"{cps:.1f} configs/s "
                       f"({cps / base_cps:.2f}x pmap monolith, "
                       f"chunk {d['chunk']}, {d['rounds']} rounds, "
                       f"{d['shard']} shards) "
                       f"[acceptance: >=1.5x pmap monolith]",
            "configs_per_sec": cps,
            "chunk": d["chunk"],
            "rounds": d["rounds"],
            "shards": d["shard"],
            "speedup_vs_pmap": cps / base_cps,
        },
    ]
