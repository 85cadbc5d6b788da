#!/usr/bin/env python3
"""Bring-up smoke of the simulator on one TPU chip, checked against the CPU.

Drives the main paths once through the entry points a user calls, at the
sizes users run, and compares every result with the same run on the CPU
backend of this process (``jax.default_device``):

  device      find the TPU; with none, exit non-zero (no CPU fallback)
  engine      memsys ``mixed`` at 64 cores through ``memsys.build`` +
              ``Simulation.run`` against the naive engine on the chip and
              the same run on the CPU; all five patterns at 16 cores,
              chip against CPU
  sweep       a 256-point ``run_sweep`` campaign with per-point horizons
              (pipelined rounds) against ``BatchRunner.run_batch`` on the
              chip, and a stratified 32-point subset on the CPU
  case        Onira microbenchmark CPIs and one TrioSim training-step
              plan, chip against CPU

Every comparison is exact equality.  Each phase prints one line with its
wall time (smoke timing, not a benchmark number); the last line of
standard output is one JSON object naming the device.

``--chips 4`` runs only the multi-chip paths and what each is compared
with: ``run_sweep(shard=4)`` against the unsharded sweep, and the
component-sharded PDES memsys on a 4-chip mesh against the same build on
four CPU devices.

Usage, from the root of a checkout:  python3 chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# sizes (module constants so a CPU rehearsal can shrink them)
ENGINE_CORES = 64          # the single-simulation memsys run
PATTERN_CORES = 16         # the five-pattern and sweep topology
N_REQS = 256               # reads per core
UNTIL = 1e7                # horizon past every drain time
PATTERNS = ("compute", "stream", "pointer", "idle_half", "mixed")
SWEEP_B = 256              # design points of the campaign
SWEEP_SPREAD = 8           # per-point horizons span [drain/8, drain]
CPU_STRIDE = 8             # every 8th point is re-run on the CPU
PDES_SHARDS = 4
PDES_TILES = 16            # memsys tiles per shard
PDES_REQS = 64
PDES_UNTIL = 1e6


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _diff(a: dict, b: dict) -> dict:
    return {k: (a.get(k), b.get(k)) for k in sorted(set(a) | set(b))
            if a.get(k) != b.get(k)}


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
def _memsys_run(dev, n_cores, pattern, naive=False, until=UNTIL):
    import jax
    from repro.sims import memsys
    with jax.default_device(dev):
        sim, st = memsys.build(n_cores=n_cores, pattern=pattern,
                               n_reqs=N_REQS, naive=naive)
        out = jax.block_until_ready(sim.run(st, until=until))
        return out, memsys.finish_stats(sim, out)


def _naive_agrees(smart, naive) -> list[str]:
    """The engine's own naive-vs-smart contract (tests/core/test_engine):
    equal component state, deliveries, progressing ticks and per-component
    busy counts; the naive engine ticks at least as often."""
    import jax
    import numpy as np
    bad = []
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(smart.comp_state),
            jax.tree.leaves(naive.comp_state)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            bad.append(jax.tree_util.keystr(path))
    for f in ("delivered", "progress_ticks", "busy"):
        if not np.array_equal(np.asarray(getattr(smart.stats, f)),
                              np.asarray(getattr(naive.stats, f))):
            bad.append(f)
    if int(smart.stats.ticks) > int(naive.stats.ticks):
        bad.append("ticks")
    return bad


def phase_engine(chip, cpu) -> tuple[list[str], str, float]:
    errs = []
    out, got = _memsys_run(chip, ENGINE_CORES, "mixed")
    _, ref = _memsys_run(cpu, ENGINE_CORES, "mixed")
    if got != ref:
        errs.append(f"{ENGINE_CORES}-core mixed chip != CPU: "
                    f"{_diff(got, ref)}")
    want = ENGINE_CORES * N_REQS
    if (got["reads_done"], got["remaining"], got["outstanding"]) \
            != (want, 0, 0):
        errs.append(f"{ENGINE_CORES}-core mixed did not drain: {got}")
    horizon = float(int(got["virtual_time"])) + 2.0
    naive, _ = _memsys_run(chip, ENGINE_CORES, "mixed", naive=True,
                           until=horizon)
    bad = _naive_agrees(out, naive)
    if bad:
        errs.append(f"naive engine disagrees with smart on {bad}")
    drain = None
    for pattern in PATTERNS:
        _, g = _memsys_run(chip, PATTERN_CORES, pattern)
        _, r = _memsys_run(cpu, PATTERN_CORES, pattern)
        if g != r:
            errs.append(f"{PATTERN_CORES}-core {pattern} chip != CPU: "
                        f"{_diff(g, r)}")
        if pattern == "mixed":
            drain = g["virtual_time"]
    detail = (f"{ENGINE_CORES}-core mixed drained at "
              f"{got['virtual_time']:.0f} cycles, {got['epochs']} epochs, "
              f"{got['reads_done']} reads; chip == CPU and naive == smart; "
              f"{len(PATTERNS)} patterns x {PATTERN_CORES} cores chip == CPU")
    return errs, detail, drain


# ---------------------------------------------------------------------------
# sweep campaign
# ---------------------------------------------------------------------------
def _sweep_points(b: int) -> list[dict]:
    """Crossbar latency and L1 boost spread over b points (the
    ``benchmarks/dse_throughput.py`` axes)."""
    return [{"conn_latency[-1]": 10.0 + (30.0 * i) / (b - 1),
             "kind.l1.extra_hit_rate": 0.8 * ((i * 7) % b) / (b - 1)}
            for i in range(b)]


def _horizons(b: int, top: float):
    """Per-point horizons spread ~SWEEP_SPREAD x up to ``top``, shuffled
    by an i*11 stride so long lanes land everywhere."""
    import numpy as np
    lo = top / SWEEP_SPREAD
    mix = (np.arange(b) * 11) % b
    return (lo + (top - lo) * mix / (b - 1)).astype(np.float32)


def _sweep_stats(sim, s) -> dict:
    """Per-point result: engine counters plus memsys statistics (host-side
    lanes, so nothing here touches a device)."""
    import numpy as np
    from repro.dse import default_extract
    cs = s.comp_state
    return dict(default_extract(sim, s),
                reads_done=int(np.sum(cs["dram"]["served"])),
                hits=int(np.sum(cs["l1"]["hits"])),
                misses=int(np.sum(cs["l1"]["misses"])),
                remaining=int(np.sum(cs["core"]["remaining"])))


def _build16(**kw):
    from repro.sims import memsys
    return memsys.build(n_cores=PATTERN_CORES, n_reqs=N_REQS, **kw)


def _sweep(dev, idx, until, shard=False) -> list[dict]:
    import jax
    from repro.dse import SweepSpec, run_sweep
    pts = _sweep_points(SWEEP_B)
    with jax.default_device(dev):
        return run_sweep(_build16, SweepSpec.explicit([pts[i] for i in idx]),
                         until=until[idx], extract=_sweep_stats, shard=shard)


def phase_sweep(chip, cpu, drain, counts) -> tuple[list[str], str]:
    import numpy as np
    from repro.dse import BatchRunner, build_param_batch, cache, stack_states
    from repro.dse import extract_rows
    b = SWEEP_B
    u = _horizons(b, drain)
    t = [time.perf_counter()]
    rows = _sweep(chip, np.arange(b), u)
    t.append(time.perf_counter())
    sim, st = _build16()
    ref_b = BatchRunner(sim).run_batch(
        stack_states(st, b), build_param_batch(sim, _sweep_points(b)), u)
    ref = extract_rows(sim, ref_b, b, extract=_sweep_stats)
    t.append(time.perf_counter())
    errs = [f"point {i}: rounds != run_batch: {_diff(rows[i], dict(p, **r))}"
            for i, (p, r) in enumerate(zip(_sweep_points(b), ref))
            if rows[i] != dict(p, **r)][:3]
    idx = np.arange(0, b, CPU_STRIDE)
    sub = _sweep(cpu, idx, u)
    t.append(time.perf_counter())
    errs += [f"point {i}: chip != CPU: {_diff(rows[i], r)}"
             for i, r in zip(idx, sub) if rows[i] != r][:3]
    cs = cache.stats()
    print(f"cache: jax compilation cache hits={counts['hits']} "
          f"misses={counts['misses']}; campaign store hits={cs['hits']} "
          f"misses={cs['misses']} writes={cs['writes']} "
          f"write_failures={cs['write_failures']}"
          + (f" ({cs['last_write_error']})" if cs["write_failures"] else ""))
    if cs["write_failures"]:
        errs.append(f"{cs['write_failures']} executable blobs not written")
    done = sum(r["remaining"] == 0 for r in rows)
    detail = (f"{b} points, horizons {float(u.min()):.0f}..{float(u.max()):.0f}"
              f" cycles, {done} drained; rounds == run_batch on the chip, "
              f"{len(idx)}-point subset chip == CPU (rounds "
              f"{t[1] - t[0]:.1f} s, run_batch {t[2] - t[1]:.1f} s, CPU "
              f"subset {t[3] - t[2]:.1f} s)")
    return errs, detail


# ---------------------------------------------------------------------------
# case studies
# ---------------------------------------------------------------------------
def _cases(dev) -> tuple[dict, dict]:
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.sims import onira, triosim
    cfg = dataclasses.replace(get_config("stablelm-1.6b"), n_layers=24)
    with jax.default_device(dev):
        cpi = onira.run_microbenches()
        step = triosim.simulate_step(cfg, batch=16, seq=1024, dp=2, tp=2,
                                     pp=2, micro=4)
    return cpi, step


def phase_cases(chip, cpu) -> tuple[list[str], str]:
    cpi, step = _cases(chip)
    cpi_ref, step_ref = _cases(cpu)
    errs = []
    if cpi != cpi_ref:
        errs.append(f"onira chip != CPU: {_diff(cpi, cpi_ref)}")
    if not all(r["done"] for r in cpi.values()):
        errs.append(f"onira microbenchmarks not done: {cpi}")
    if step != step_ref:
        errs.append(f"triosim chip != CPU: {_diff(step, step_ref)}")
    if not step["done"]:
        errs.append(f"triosim step not done: {step}")
    detail = (f"onira {len(cpi)} CPIs chip == CPU; triosim stablelm-1.6b "
              f"dp2/tp2/pp2 step {step['step_us']:.1f} us chip == CPU")
    return errs, detail


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def phase_sharded_sweep(chip, drain) -> tuple[list[str], str]:
    import numpy as np
    idx = np.arange(SWEEP_B)
    u = _horizons(SWEEP_B, drain)
    rows = _sweep(chip, idx, u, shard=PDES_SHARDS)
    ref = _sweep(chip, idx, u)
    errs = [f"point {i}: shard={PDES_SHARDS} != unsharded: "
            f"{_diff(a, b)}" for i, (a, b) in enumerate(zip(rows, ref))
            if a != b][:3]
    return errs, (f"{SWEEP_B} points, shard={PDES_SHARDS} rows == unsharded "
                  f"rows on one chip")


def _pdes(devices):
    import jax
    from repro.launch.mesh import make_sim_mesh
    from repro.sims.memsys import build_sharded_memsys
    with jax.default_device(devices[0]):
        ss = build_sharded_memsys(
            mesh=make_sim_mesh(PDES_SHARDS, devices=devices),
            n_shards=PDES_SHARDS, tiles_per_shard=PDES_TILES,
            n_reqs=PDES_REQS)
        out, windows = ss.run(ss.shard_state(ss.init_state()),
                              until=PDES_UNTIL, return_windows=True)
        return jax.device_get(out), windows


def phase_pdes(chips, cpus) -> tuple[list[str], str]:
    import jax
    import numpy as np
    out, windows = _pdes(chips)
    ref, ref_windows = _pdes(cpus)
    errs = [f"{jax.tree_util.keystr(p)} chip != CPU"
            for (p, a), b in zip(jax.tree_util.tree_leaves_with_path(out),
                                 jax.tree.leaves(ref))
            if not np.array_equal(a, b)][:3]
    if windows != ref_windows:
        errs.append(f"windows chip {windows} != CPU {ref_windows}")
    # conservation (tests/launch/test_dryrun_small.py): every remote write
    # issued and served by the neighbour's DRAM, every local read retired
    served = out.comp_state["dram"]["served"].reshape(PDES_SHARDS, -1)
    core = out.comp_state["core"]
    if out.comp_state["writer"]["remaining"].sum() != 0 \
            or not (served.sum(axis=1) >= PDES_REQS).all() \
            or core["remaining"].sum() != 0 or core["outstanding"].sum() != 0:
        errs.append(f"PDES conservation failed: served {served.tolist()}")
    return errs, (f"{PDES_SHARDS} shards x {PDES_TILES} tiles, {windows} "
                  f"windows, served {served.sum(axis=1).tolist()}; chip mesh "
                  f"== CPU mesh")


# ---------------------------------------------------------------------------
def run_phases(chips: int, devs, cpus) -> list[str]:
    """Run the phases for ``chips`` (1: engine, sweep, case studies; 4:
    the multi-chip paths) on ``devs`` against ``cpus``; returns the names
    of the phases that failed."""
    import jax
    from jax._src import monitoring
    from repro.dse import cache
    counts = {"hits": 0, "misses": 0}

    def _listen(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1
    monitoring.register_event_listener(_listen)
    cache.enable_jax_cache()
    cache.configure(os.environ.get(cache.ENV_DIR)
                    or os.path.join(ROOT, ".repro_cache"))

    failed = []

    def phase(name, fn, *a):
        t = time.perf_counter()
        try:
            errs, detail, *rest = fn(*a)
        except Exception as e:           # report, then fail the run
            errs, detail, rest = [repr(e)], "raised", [None]
        ok = not errs
        print(f"phase {name}: {'ok' if ok else 'FAILED'}, wall "
              f"{time.perf_counter() - t:.1f} s (smoke timing, not a "
              f"benchmark number); {detail}", flush=True)
        for e in errs:
            print(f"  {name}: {e}", flush=True)
        if not ok:
            failed.append(name)
        return rest[0] if rest else None

    chip, cpu = devs[0], cpus[0]
    if chips == 4:
        from repro.sims import memsys
        with jax.default_device(chip):
            sim, st = memsys.build(n_cores=PATTERN_CORES, n_reqs=N_REQS)
            drain = float(sim.run(st, until=UNTIL).time)
        phase("sharded_sweep", phase_sharded_sweep, chip, drain)
        phase("pdes", phase_pdes, devs[:PDES_SHARDS], cpus[:PDES_SHARDS])
    else:
        drain = phase("engine", phase_engine, chip, cpu)
        if drain is None:
            failed.append("sweep")
        else:
            phase("sweep", phase_sweep, chip, cpu, drain, counts)
        phase("case_studies", phase_cases, chip, cpu)
    return failed


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip paths")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no src/repro beside {__file__}: run it from a checkout")
    sys.path.insert(0, SRC)

    import jax
    if args.chips == 4:
        # four CPU devices for the PDES reference; set before any backend
        # comes up
        jax.config.update("jax_num_cpu_devices", PDES_SHARDS)
    t0 = time.perf_counter()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"no TPU found (JAX sees {devs[0].platform} devices); this "
              "smoke runs on the chip only")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPU chips, JAX sees "
              f"{len(devs)}")
    cpus = jax.devices("cpu")
    print(f"phase device: ok, wall {time.perf_counter() - t0:.1f} s (smoke "
          f"timing, not a benchmark number); {len(devs)} x "
          f"{devs[0].device_kind}, CPU reference {cpus[0]}", flush=True)

    failed = run_phases(args.chips, devs, cpus)
    if failed:
        _fail(f"failed phases: {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
