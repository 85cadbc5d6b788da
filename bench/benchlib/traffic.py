"""The general traffic generator: seeds, design points and horizons from
a traffic file's parameters.

Every cell's traffic is a JSON file under ``bench/traffic/``; the
functions here read its parameters and nothing else, so a new mix is a
new data file.  The generator is the benchmark's own copy of the program's
(``SweepSpec.random`` per-axis substreams), so a change to the program
cannot change the yardstick.
"""
from __future__ import annotations

import zlib

import numpy as np

SEED_MASK = (1 << 64) - 1


def job_seed(seed: int, k: int) -> int:
    """Seed of job ``k`` of a run started with ``seed`` (any integer)."""
    ss = np.random.SeedSequence([int(seed) & SEED_MASK, int(k)])
    return int(ss.generate_state(1, np.uint32)[0])


def job_rng(seed: int, k: int) -> np.random.Generator:
    return np.random.default_rng(job_seed(seed, k))


def random_points(axes: dict, n: int, seed: int) -> list[dict]:
    """``n`` points drawn independently per axis, each ``[lo, hi]``:
    int endpoints draw ints on the inclusive range, float endpoints
    uniform floats.  Each axis has its own stream keyed on
    ``(seed, crc32(axis name))``, as ``SweepSpec.random`` draws them."""
    cols = {}
    for name, (lo, hi) in axes.items():
        rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
        if isinstance(lo, int) and isinstance(hi, int):
            cols[name] = [int(v) for v in rng.integers(lo, hi + 1, n)]
        else:
            cols[name] = [float(v) for v in
                          rng.uniform(float(lo), float(hi), n)]
    return [{name: cols[name][i] for name in axes} for i in range(n)]


def points(traffic: dict, seed: int) -> list[dict]:
    """The design points of one campaign of ``traffic``."""
    if traffic["sampler"] != "random":
        raise ValueError(f"unknown sampler {traffic['sampler']!r}")
    return random_points(traffic["axes"], traffic["points"], seed)


def until(traffic: dict) -> np.ndarray:
    """Per-point horizons of one campaign of ``traffic``."""
    return np.full(traffic["points"], traffic["until"], np.float32)
