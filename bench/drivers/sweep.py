"""Driver ``sweep``: whole design-space campaigns back to back through
``repro.dse.run_sweep``, with the round schedule the traffic pins.

Traffic parameters: ``points`` per campaign, ``sampler`` and ``axes``
(see ``benchlib.traffic``), ``until`` (a horizon past every drain),
``schedule`` (the round schedule, ``top`` rung and epoch ``quantum``;
see below) and ``check_points`` (how many of the window's points the
reference re-runs; the point that took the most epochs is always among
them).

Each job is a campaign of its own: job ``k`` of a run draws its points
and simulated inputs from the run's seed and ``k``, and the untimed
warm-up draws from a stream no timed job uses.

The round loop compiles one program per shape of the batches it
gathers and concatenates, and those shapes follow how many lanes
survive each round.  With ``run_sweep``'s default schedule the epoch
quantum grows by the wall-clock time of each round, so the shapes, and
the programs compiled inside the window, change from run to run.  The
traffic therefore pins the schedule: with a quantum past every lane's
drain each round runs its lanes to the end, and the rounds' shapes
depend on the campaign alone."""
from __future__ import annotations

from repro.dse import ChunkSchedule, SweepSpec, make_ladder, run_sweep

from benchlib import compare, traffic as tr
from benchlib.harness import WARM_STREAM, load_module, span


class Driver:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.config, self.traffic = cell.config, cell.traffic
        self.ref = load_module("reference", cell.system)
        self.until = tr.until(self.traffic)
        self.system = None

    def _campaign(self, k: int):
        inputs = self.ref.inputs(self.config, tr.job_rng(self.seed, k))
        return inputs, tr.points(self.traffic, tr.job_seed(self.seed, k))

    def _run(self, k: int) -> list[dict]:
        inputs, pts = self._campaign(k)
        sched = self.traffic["schedule"]
        schedule = ChunkSchedule(make_ladder(len(pts), top=sched["top"]),
                                 quantum=sched["quantum"])
        with span("run_sweep"):
            return run_sweep(self.system.build_fn(inputs),
                             SweepSpec.explicit(pts), until=self.until,
                             extract=self.system.extract, schedule=schedule)

    def setup(self) -> None:
        self.system = load_module("systems", self.cell.system).System(
            self.config)
        self._run(WARM_STREAM)                     # the untimed warm-up

    def job(self, k: int) -> dict:
        rows = self._run(k)
        return {"k": k, "rows": rows, "points": len(rows)}

    def describe(self) -> str:
        from repro.dse.runner import runner_for
        lr = runner_for(self.system.sim).last_rounds or {}
        return (f"campaigns of {self.traffic['points']} points; last "
                f"campaign: chunk {lr.get('chunk')}, rounds "
                f"{lr.get('rounds')}, quantum {lr.get('quantum')}")

    def release(self) -> None:
        self.system = None

    def attempted(self, jobs) -> int:
        return sum(j["points"] for j in jobs)

    def failed(self, jobs) -> int:
        """Points that ran out of ``run_sweep``'s default epoch budget."""
        return sum(r["epochs"] >= 2_000_000 for j in jobs for r in j["rows"])

    def check(self, jobs, rng, control: bool = False) -> list:
        """The point that took the most epochs and ``check_points - 1``
        others drawn from ``rng``, each re-run by the plain reference
        with its campaign's inputs; with ``control`` the control stands
        in for the program's results."""
        flat = [(j["k"], i) for j in jobs for i in range(j["points"])]
        rows = {j["k"]: j["rows"] for j in jobs}
        longest = max(range(len(flat)),
                      key=lambda n: rows[flat[n][0]][flat[n][1]]["epochs"])
        rest = [n for n in range(len(flat)) if n != longest]
        n = min(len(rest), self.traffic["check_points"] - 1)
        picks = [longest] + sorted(
            int(x) for x in rng.choice(rest, n, replace=False))
        pairs = []
        for n in picks:
            k, i = flat[n]
            inputs, pts = self._campaign(k)
            until = float(self.until[i])
            ref = self.ref.simulate(self.config, inputs, pts[i], until)
            got = compare.control(self.ref.simulate, self.config, inputs,
                                  pts[i], until, ref) if control \
                else rows[k][i]
            pairs.append((f"campaign {k} point {i} {pts[i]}", got, ref))
        return pairs
