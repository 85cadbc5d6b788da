"""Structural (topology-shape) sweep throughput: a masked topology family
versus the per-shape rebuild+recompile workflow it replaces (DSE.md
"Topology families").

A 4-point ``shape.core`` ∈ {1, 2, 4, 8} sweep of the memsys hierarchy:

* ``rebuild_baseline`` — one ``build(n_cores=S)`` + jit compile + run per
  shape: what structural DSE costs when instance counts are build-time
  constants (every ``static.*`` point is its own compile group).
* ``family_cold`` — one padded family build + ONE compile of the vmapped
  masked batch + the batched run (end-to-end, first-call cost).
* ``family_warm`` — the batched run alone (steady-state sweep cost: in a
  DSE campaign the single family compile amortizes across every round;
  the ≥5x CI acceptance bar compares this rate against the rebuild
  baseline, which pays compilation per point forever).

Plus the *cross-process* cold start (DSE.md "Sharded sweeps and the
persistent cache"): two fresh subprocesses share a campaign cache dir
and each times the family build + compile + run from post-import.

* ``family_cold_uncached`` — the first process: every executable is an
  actual XLA compile (persistent-cache misses).
* ``family_cold_cached``   — the second process: every executable
  deserializes from the persistent compilation cache — **zero** misses,
  and the ≥5x CI bar gates the wall-clock ratio against the uncached
  run.

The two cold-start processes run first, while this process has not yet
touched JAX (a parent that holds the accelerator would starve them),
and share one fixed campaign cache dir and one fixed JAX compilation
cache inside the checkout, emptied beforehand so the first is uncached.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COLD_CACHE = os.path.join(ROOT, ".repro_cache", "struct_sweep_cold")

SHAPES = (1, 2, 4, 8)
UNTIL = 50000.0
N_REQS = 24

_COLD_WORKER = textwrap.dedent("""
    import json, time
    import jax
    # persistent-cache traffic: a miss is an actual XLA compile
    from jax._src import monitoring
    C = {"hits": 0, "misses": 0}
    def _l(event):
        if event == "/jax/compilation_cache/cache_hits":
            C["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            C["misses"] += 1
    monitoring.register_event_listener(lambda e, **kw: _l(e))
    from repro.dse import (BatchRunner, stack_params, stack_state_list,
                           cache as dse_cache)
    from repro.sims.memsys import build_family
    assert dse_cache.active(), "REPRO_CACHE_DIR not picked up"
    SHAPES, UNTIL, N_REQS = %r, %r, %r
    t0 = time.perf_counter()
    fam = build_family(n_cores=max(SHAPES), pattern="mixed",
                       n_reqs=N_REQS, donate=True)
    runner = BatchRunner(fam.sim)
    pb = stack_params([fam.params_for({"core": s}) for s in SHAPES])
    sb = stack_state_list([fam.state_for({"core": s}) for s in SHAPES])
    out = runner.run_batch(sb, pb, UNTIL)
    out.time.block_until_ready()
    dt = time.perf_counter() - t0
    print(json.dumps({"seconds": dt,
                      "rows": [float(t) for t in out.time.tolist()],
                      **C}))
""") % (SHAPES, UNTIL, N_REQS)


def _cold_run():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["REPRO_CACHE_DIR"] = os.path.join(COLD_CACHE, "campaign")
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(COLD_CACHE, "jax")
    r = subprocess.run([sys.executable, "-c", _COLD_WORKER],
                       capture_output=True, text=True, timeout=1800,
                       env=env)
    if r.returncode != 0:
        raise RuntimeError(f"cold-start worker failed: {r.stderr[-3000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def _family_batch(fam):
    import jax
    from repro.dse import stack_params, stack_state_list
    pb = stack_params([fam.params_for({"core": s}) for s in SHAPES])
    sb = stack_state_list([fam.state_for({"core": s}) for s in SHAPES])
    return jax.block_until_ready(sb), jax.block_until_ready(pb)


def bench():
    # two-process persistent-cache cold start: same family workload,
    # fresh interpreter each time, shared campaign cache dir — before
    # this process touches JAX
    shutil.rmtree(COLD_CACHE, ignore_errors=True)
    uncached = _cold_run()
    cached = _cold_run()

    from repro.dse import BatchRunner
    from repro.sims.memsys import build, build_family
    rows = []
    n = len(SHAPES)

    # baseline: rebuild + recompile + run per shape
    t0 = time.perf_counter()
    for s in SHAPES:
        sim, st = build(n_cores=s, pattern="mixed", n_reqs=N_REQS,
                        donate=True)
        out = sim.run(st, UNTIL)
        out.time.block_until_ready()
    dt_base = time.perf_counter() - t0
    base_cps = n / dt_base
    rows.append({
        "name": "struct_sweep/rebuild_baseline",
        "us_per_call": dt_base / n * 1e6,
        "derived": f"{base_cps:.2f} shapes/s (one build+compile+run per "
                   f"shape, {n}-shape sweep)",
        "configs_per_sec": base_cps,
    })

    # family: one padded build, one compile, every shape a masked lane
    t0 = time.perf_counter()
    fam = build_family(n_cores=max(SHAPES), pattern="mixed", n_reqs=N_REQS,
                       donate=True)
    runner = BatchRunner(fam.sim)
    sb, pb = _family_batch(fam)
    out = runner.run_batch(sb, pb, UNTIL)
    out.time.block_until_ready()
    dt_cold = time.perf_counter() - t0
    rows.append({
        "name": "struct_sweep/family_cold",
        "us_per_call": dt_cold * 1e6,
        "derived": f"{n / dt_cold:.2f} shapes/s incl. the one family "
                   f"build+compile ({(n / dt_cold) / base_cps:.2f}x the "
                   f"rebuild baseline even end-to-end)",
        "configs_per_sec": n / dt_cold,
        "speedup_vs_rebuild": (n / dt_cold) / base_cps,
    })

    sb, pb = _family_batch(fam)     # fresh states; executable is cached
    t0 = time.perf_counter()
    out = runner.run_batch(sb, pb, UNTIL)
    out.time.block_until_ready()
    dt_warm = time.perf_counter() - t0
    warm_cps = n / dt_warm
    rows.append({
        "name": "struct_sweep/family_warm",
        "us_per_call": dt_warm * 1e6,
        "derived": f"{warm_cps:.1f} shapes/s "
                   f"({warm_cps / base_cps:.1f}x per-shape rebuild) "
                   f"[acceptance: >=5x rebuild]",
        "configs_per_sec": warm_cps,
        "speedup_vs_rebuild": warm_cps / base_cps,
    })

    if cached["rows"] != uncached["rows"]:
        raise RuntimeError(
            f"cached cold start changed rows: {cached['rows']} "
            f"vs {uncached['rows']}")
    speedup = uncached["seconds"] / cached["seconds"]
    rows.append({
        "name": "struct_sweep/family_cold_uncached",
        "us_per_call": uncached["seconds"] * 1e6,
        "derived": f"{uncached['seconds']:.2f} s fresh-process family "
                   f"build+compile+run ({uncached['misses']} XLA "
                   f"compiles persisted)",
        "seconds": uncached["seconds"],
        "compile_cache_misses": uncached["misses"],
        "compile_cache_hits": uncached["hits"],
    })
    rows.append({
        "name": "struct_sweep/family_cold_cached",
        "us_per_call": cached["seconds"] * 1e6,
        "derived": f"{cached['seconds']:.2f} s second-process cold start "
                   f"({speedup:.1f}x faster, {cached['misses']} compiles, "
                   f"{cached['hits']} persistent-cache hits) "
                   f"[acceptance: >=5x uncached, zero compiles]",
        "seconds": cached["seconds"],
        "compile_cache_misses": cached["misses"],
        "compile_cache_hits": cached["hits"],
        "speedup_vs_uncached": speedup,
    })
    return rows
