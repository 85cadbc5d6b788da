#!/usr/bin/env python3
"""Readings behind the limit of ``correct``: on each seed, the numbers
the check compares for the program's own jobs and for the control (the
plain reference in bfloat16 time put in the program's place), on the
same jobs, in one process so that set-up is paid once.

    python3 bench/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

from the root of a checkout on the chip.  Prints one JSON line per seed.
The benchmark's own runs (``run.py``) never run the control.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import compare, harness, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, args.workload)
    try:
        harness.start(cell)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    driver = harness.load_module("drivers", cell.traffic["driver"]).Driver(
        cell, args.seeds[0])
    driver.setup()
    runs = []
    for seed in args.seeds:
        driver.seed = seed
        run = harness.Run(cell)
        harness.measure(driver, run, args.seconds, False,
                        lambda m: print(m, file=sys.stderr, flush=True))
        runs.append((seed, run))
    driver.release()
    for seed, run in runs:
        driver.seed = seed
        line = {"seed": seed, "jobs": len(run.jobs)}
        for side in ("program", "control"):
            t = time.perf_counter()
            verdict = compare.judge(driver.check(
                run.jobs, traffic.job_rng(seed, harness.CHECK_STREAM),
                control=side == "control"))
            line[side] = {k: v for k, (v, _) in verdict["numbers"].items()}
            line[side + "_correct"] = verdict["correct"]
            line[side + "_s"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
