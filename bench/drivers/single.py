"""Driver ``single``: one simulation at a time, run to drain through
``Simulation.run``, each job with inputs drawn from its own seed.

Traffic parameters: ``until`` (a horizon past every drain),
``warm_until`` (the horizon of the untimed warm-up job: the horizon is
an operand of the one compiled loop, so a short job warms every program
a timed job runs) and ``check_jobs`` (how many of the window's jobs the
reference re-runs; the longest is always among them)."""
from __future__ import annotations

import jax

from benchlib import compare
from benchlib.harness import WARM_STREAM, load_module, span
from benchlib.traffic import job_rng


class Driver:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.ref = load_module("reference", cell.system)
        self.system = None

    def _inputs(self, k: int) -> dict:
        return self.ref.inputs(self.config, job_rng(self.seed, k))

    def _one(self, inputs: dict, until: float) -> dict:
        with span("state.build"):
            st = self.system.state(inputs)
        with span("sim.run"):
            out = jax.block_until_ready(self.system.run(st, until))
        with span("extract"):
            return self.system.stats(out)

    def setup(self) -> None:
        self.system = load_module("systems", self.cell.system).System(
            self.config)
        self._one(self._inputs(WARM_STREAM), self.traffic["warm_until"])

    def job(self, k: int) -> dict:
        stats = self._one(self._inputs(k), self.traffic["until"])
        return {"k": k, "stats": stats, "sim_cycles": stats["virtual_time"],
                "epochs": stats["epochs"]}

    def describe(self) -> str:
        return f"{self.config['cores']}-core runs to drain"

    def release(self) -> None:
        self.system = None

    def attempted(self, jobs) -> int:
        return len(jobs)

    def failed(self, jobs) -> int:
        """Jobs that did not drain before the horizon."""
        return sum(j["stats"]["remaining"] != 0
                   or j["stats"]["outstanding"] != 0 for j in jobs)

    def check(self, jobs, rng, control: bool = False) -> list:
        """The longest job and ``check_jobs - 1`` others drawn from
        ``rng``, each re-run by the plain reference; with ``control``
        the control stands in for the program's results."""
        longest = max(range(len(jobs)), key=lambda i: jobs[i]["epochs"])
        rest = [i for i in range(len(jobs)) if i != longest]
        n = min(len(rest), self.traffic["check_jobs"] - 1)
        picks = [longest] + sorted(
            int(i) for i in rng.choice(rest, n, replace=False))
        pairs = []
        for i in picks:
            inputs = self._inputs(jobs[i]["k"])
            until = self.traffic["until"]
            ref = self.ref.simulate(self.config, inputs, {}, until)
            got = compare.control(self.ref.simulate, self.config, inputs,
                                  {}, until, ref) if control \
                else jobs[i]["stats"]
            pairs.append((f"job {jobs[i]['k']}", got, ref))
        return pairs
