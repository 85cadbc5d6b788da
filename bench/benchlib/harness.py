"""One run of one benchmark cell: resolve its files by name, set up,
measure a closed loop of whole jobs for ``--seconds``, check the jobs
against the plain reference and assemble the result line.

Everything that belongs to one cell lives in files found by the names in
``BENCHMARK.json``:

* ``bench/configs/<file>``: the simulated system's sizes; its
  ``system`` key names ``bench/systems/<system>.py`` (how the program
  under test builds and runs it) and ``bench/reference/<system>.py``
  (the plain reference and the generator of its inputs);
* ``bench/traffic/<traffic>.json``: the job mix; its ``driver`` key
  names ``bench/drivers/<driver>.py``, and its ``trace_s`` the longest
  slice a traced run records (see ``TRACE_AT``);
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number or ``None`` when the run has nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# the spans the harness and its drivers record around their calls into
# the program; the trace reduction labels idle gaps with them
SPANS = ("setup", "window", "state.build", "sim.run", "run_sweep",
         "extract", "trace.begin", "trace.end")
# the traced run records one slice of its window: it starts at the first
# job boundary TRACE_AT of the way into --seconds, so that it reads the
# same phase of a job in every run, and ends with that job or after the
# traffic's ``trace_s`` seconds, whichever comes first.  A whole window of
# this simulator's microsecond-sized device ops would make a trace of
# gigabytes, and on the TPU a second profiler session in one process may
# record no device operations at all
TRACE_AT = 0.3
# the seed streams of the untimed warm-up job and of the choice of jobs
# to check (timed jobs count up from 0 and never reach them)
WARM_STREAM, CHECK_STREAM = 1 << 30, 1 << 31


class BenchError(Exception):
    """The benchmark cannot run here (no chip, missing files, a
    configuration the program does not build as stated)."""


def require_built(what: str, stated, built) -> None:
    """Refuse a configuration value that the program under test did not
    build as the configuration states it."""
    if stated != built:
        raise BenchError(f"the configuration states {what} = {stated!r}, "
                         f"the program built {built!r}")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    with open(path) as fh:
        return json.load(fh)


def _covers(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, bench: dict, name: str):
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise BenchError(
                f"no workload {name!r}; have "
                f"{[w['name'] for w in bench['workloads']]}")
        self.workload = found[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.workload["config"])
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", self.workload["traffic"] + ".json"))
        self.system = self.config["system"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _covers(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _covers(m, name)]


class CompileCounter:
    """Counts executables JAX builds (from its cache or by compiling)
    and real compiles (persistent-cache misses), process-wide."""

    def __init__(self):
        from jax._src import monitoring
        self.built = 0
        self.misses = 0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.built += 1

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> tuple[int, int]:
        return self.built, self.misses


class RoundSink:
    """Keeps the round loop's ``round.end`` events from the program's
    telemetry bus."""

    def __init__(self):
        self.events: list[dict] = []

    def on_event(self, ev: dict) -> None:
        if ev["kind"] == "round.end":
            self.events.append(ev)


class TraceSlice:
    """One profiler slice from a job boundary: ``begin`` starts it, and
    it stops when ``job_done`` is called or after ``length`` seconds,
    whichever comes first.  Both ends are marked with host spans
    (``trace.begin``, ``trace.end``) on the profiler's clock."""

    def __init__(self, logdir: str, length: float):
        self.logdir, self.length = logdir, length
        self.done = threading.Event()
        self.thread = threading.Thread(target=self._stop, daemon=True)

    def begin(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        # no tracing of Python calls: it slowed an Onira campaign by a
        # quarter, and nothing reads it; the harness's spans stay
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        with span("trace.begin"):
            pass
        self.thread.start()

    def _stop(self) -> None:
        import jax
        self.done.wait(self.length)
        with span("trace.end"):
            pass
        jax.profiler.stop_trace()

    def job_done(self) -> None:
        self.done.set()


class Run:
    """What the metric readers see of one run."""

    def __init__(self, cell: Cell):
        self.cell = cell
        self.setup_s = 0.0
        self.window_s = 0.0
        self.jobs: list[dict] = []
        self.rounds: list[dict] = []
        self.trace: dict | None = None


def span(name: str):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def measure(driver, run: Run, seconds: float, trace: bool,
            log=print) -> None:
    """The measured window: whole jobs back to back until ``seconds``
    have passed; the window ends when the last job that started in it
    ends.  With ``trace`` the profiler and the program's round telemetry
    record it."""
    from repro.obs.bus import BUS

    counter = CompileCounter()
    sink = RoundSink()
    logdir = os.path.join(ROOT, ".bench_trace", run.cell.name)
    tracer = None
    if trace:
        import shutil
        shutil.rmtree(logdir, ignore_errors=True)
        BUS.attach(sink)
    built0, miss0 = counter.snapshot()
    t0 = time.perf_counter()
    with span("window"):
        k = 0
        while True:
            if trace and tracer is None and \
                    time.perf_counter() - t0 >= TRACE_AT * seconds:
                tracer = TraceSlice(logdir, run.cell.traffic["trace_s"])
                tracer.begin()
            rec = driver.job(k)
            if tracer is not None:
                tracer.job_done()
            rec["end"] = time.perf_counter() - t0
            run.jobs.append(rec)
            k += 1
            if rec["end"] >= seconds:
                break
    run.window_s = time.perf_counter() - t0
    built1, miss1 = counter.snapshot()
    if trace:
        BUS.detach(sink)
        run.rounds = sink.events
    log(f"window: {len(run.jobs)} jobs in {run.window_s:.3f} s; "
        f"executables built in the window {built1 - built0} (compiled "
        f"{miss1 - miss0}); {driver.describe()}")
    if trace:
        if tracer is None:
            raise BenchError(f"no job started {TRACE_AT} of the way into "
                             f"the window to trace; give it more --seconds")
        tracer.thread.join()
        from benchlib import trace as trace_mod
        t = time.perf_counter()
        run.trace = trace_mod.summarize(logdir, SPANS)
        log(f"trace: a slice of {run.trace['window_s']:.3f} s, device busy "
            f"{run.trace['busy_s']:.6f} s of it; reduced in "
            f"{time.perf_counter() - t:.1f} s")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, devices, log=print) -> dict:
    """Set up, measure and check one run; returns the result object."""
    from benchlib import compare, traffic as tr

    driver = load_module("drivers", cell.traffic["driver"]).Driver(
        cell, seed)
    run = Run(cell)
    t_ready = time.perf_counter() - t_start
    with span("setup"):
        driver.setup()
    run.setup_s = time.perf_counter() - t_start
    log(f"setup: chip and caches ready at {t_ready:.2f} s, cell built and "
        f"warmed up at {run.setup_s:.2f} s")
    measure(driver, run, seconds, trace, log)
    device = device_info(devices, cell.chips)
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    driver.release()

    t = time.perf_counter()
    pairs = driver.check(run.jobs, tr.job_rng(seed, CHECK_STREAM))
    verdict = compare.judge(pairs)
    log(f"check: {len(pairs)} jobs against the plain reference in "
        f"{time.perf_counter() - t:.1f} s")
    for line in verdict["shown"]:
        log(f"  mismatch {line}")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": verdict["correct"],
              "attempted": driver.attempted(run.jobs),
              "failed": driver.failed(run.jobs),
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, (v, lim) in verdict["numbers"].items()}
    return result


def start(cell: Cell):
    """Put the program on the path, fix its cache directories inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` wins if set, so that only a
    cell's first run compiles) and find the chips; returns the devices.
    Raises :class:`BenchError` where the cell cannot run."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError("no src/repro beside the benchmark: run it from "
                         "a checkout of the repository")
    sys.path.insert(0, src)
    os.environ.setdefault("REPRO_CACHE_DIR",
                          os.path.join(ROOT, ".repro_cache"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {devices[0].platform} devices, "
                         "and this benchmark runs on the chip only")
    if len(devices) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX sees "
                         f"{len(devices)}")
    from repro.dse import cache
    cache.enable_jax_cache()
    cache.configure(os.environ["REPRO_CACHE_DIR"])
    return devices


def main(argv=None, t_start: float | None = None) -> int:
    """The command line: one run of one cell; prints the result as the
    last line of standard output.  ``t_start`` is when the process
    started (``setup_s`` counts from it)."""
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = Cell(bench, args.workload)
        devices = start(cell)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start, devices, log)
    for name, c in result["compared"].items():
        log(f"compared {name} = {c['value']} (limit {c['limit']})")
    log(f"correct = {str(result['correct']).lower()}")
    print(json.dumps(result), flush=True)
    return 0
