"""Cells of the benchmark cut to a size the CPU test run can hold: the
same files, drivers and code paths, with fewer cores, reads and points.
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import harness  # noqa: E402

BENCHMARK = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
SEED = 2**31 + 977         # past 32 signed bits, as the driver's are


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.Cell(BENCHMARK, name)
    tr = dict(cell.traffic)
    if cell.system == "memsys":
        cell.config = dict(cell.config, cores=4, reads_per_core=12)
    if tr["driver"] == "sweep":
        tr.update(points=8, check_points=8)
    cell.traffic = tr
    return cell


def run_tiny(name: str, seconds: float = 0.3, seed: int = SEED) -> dict:
    import jax
    return harness.run_cell(tiny_cell(name), seed, seconds, False, 0.0,
                            jax.devices(), log=lambda m: None)
