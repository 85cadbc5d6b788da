"""Record ``data/trace_spans.xplane.pb.gz``, the small chip trace on which
``test_progtrace.py`` checks the span and scope reduction.

    python3 bench/tests/record_trace.py <out.xplane.pb.gz>

on a host with one TPU, from the root of a checkout.  It warms up, then
traces one window (``trace.begin`` to ``trace.end``) that holds a 4-core
memsys run to 200 cycles (``Simulation.run``: the engine's scopes and
``engine.*`` spans) and a 4-point Onira campaign through ``run_sweep``
(the ``sweep.*`` and ``round.*`` spans), and writes the profiler's
``.xplane.pb`` gzipped.
"""
import glob
import gzip
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def main(out: str) -> None:
    import jax
    from benchlib import harness
    from repro.dse import ChunkSchedule, SweepSpec, make_ladder, run_sweep
    from repro.dse.cache import enable_jax_cache
    from repro.sims.memsys import build

    enable_jax_cache()        # keys cached programs on their scopes too

    system = harness.load_module("systems", "onira").System(
        harness.load_json(os.path.join(BENCH, "configs",
                                       "onira-rv-inorder.json")))
    points = SweepSpec.explicit(
        [{"conn_latency": 1.0 + 9 * i, "kind.cpu.flush_cycles": 1 + i}
         for i in range(4)])
    schedule = ChunkSchedule(make_ladder(4, top=4), quantum=1 << 20)

    sim, _ = build(n_cores=4, pattern="mixed", n_reqs=8)

    def jobs():
        st = sim.init_state()
        with harness.span("sim.run"):
            jax.block_until_ready(sim.run(st, 200.0))
        with harness.span("run_sweep"):
            run_sweep(system.build_fn({}), points, until=60.0,
                      extract=system.extract, schedule=schedule)

    jobs()                                   # compile everything first
    logdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with harness.span("trace.begin"):
        pass
    jobs()
    with harness.span("trace.end"):
        pass
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    with open(path, "rb") as src, gzip.open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(logdir)
    print(f"{out}: {os.path.getsize(out)} bytes")


if __name__ == "__main__":
    main(sys.argv[1])
