"""Benchmark harness — one module per paper table/figure.

  Fig 9a/9b  smart_ticking        speedup + accuracy
  Fig 10     parallel_sim         transparent parallelism scaling
  Fig 11     tracing_overhead     tracer-mix slowdown
  Fig 12/13  onira_cpi            RISC-V timing-model CPI accuracy
  Fig 14     triosim_validation   DP/TP/PP step-time validation
  (framework) kernels             attention/SSD algorithm benchmarks
  (dse)      dse_throughput       batched-sweep configs/sec (DSE.md)
  (dse)      struct_sweep         topology-family shape sweep vs per-shape
                                  rebuild+recompile (DSE.md families) +
                                  two-process persistent-cache cold start
  (dse)      search_convergence   successive-halving search vs exhaustive
                                  sweep: objective gap + cycle budget
                                  (DSE.md "Search")
  (dse)      sharded_sweep        sharded rounds vs the monolithic pmap
                                  round on >= 2 devices (DSE.md "Sharded
                                  sweeps")

Prints ``name,us_per_call,derived`` CSV.  The harness itself never
touches JAX: each module runs in a fresh child process of its own, so a
module that needs the accelerator (or starts processes of its own, as
``struct_sweep`` does before it touches JAX) never finds it held by a
parent.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

MODULES = ("smart_ticking", "parallel_sim", "tracing_overhead", "onira_cpi",
           "triosim_validation", "kernels", "pdes_scaling", "dse_throughput",
           "struct_sweep", "search_convergence", "sharded_sweep")

# the child: import one module, run it, print its rows as the last line
_CHILD = ("import json, sys; from benchmarks import {0} as m; "
          "print(json.dumps(m.bench(), default=float))")


def _bench(name: str) -> list[dict]:
    """Run ``benchmarks.<name>.bench()`` in a child process (stderr passes
    through) and return its rows; raises on a non-zero exit."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _CHILD.format(name)],
                       cwd=root, stdout=subprocess.PIPE, text=True)
    if r.returncode != 0:
        raise RuntimeError(f"exit code {r.returncode}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset of benchmark module names")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write per-benchmark results (us_per_call + "
                         "derived metrics) as JSON, e.g. BENCH_engine.json, "
                         "so future PRs have a perf trajectory to compare")
    args = ap.parse_args()
    modules = [m for m in MODULES if not args.only or m in args.only]

    print("name,us_per_call,derived")
    failures = 0
    results: dict[str, dict] = {}
    for name in modules:
        try:
            for row in _bench(name):
                print(f"{row['name']},{row['us_per_call']:.1f},"
                      f"\"{row['derived']}\"")
                sys.stdout.flush()
                results[row["name"]] = {
                    k: v for k, v in row.items() if k != "name"}
        except Exception as e:  # keep the harness going, report at exit
            failures += 1
            print(f"{name},ERROR,\"{e!r}\"")
            results[name] = {"error": repr(e)}
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(results, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
