"""The Onira configuration as the program under test builds and runs
it: ``repro.sims.onira.build_onira`` over the benchmark's copies of the
microbenchmark programs, one core and memory pair per program.

Every size of the configuration is either passed to the program's
builder or checked against what it built, so a configuration the
program does not build as stated is refused before any run."""
from __future__ import annotations

import numpy as np

from repro.sims import onira

from benchlib.harness import require_built


class System:
    def __init__(self, config: dict):
        self.config = config
        progs = []
        for name in config["program_order"]:
            p = np.zeros((config["program_slots"], 4), np.int32)
            body = np.asarray(config["programs"][name], np.int32)
            p[:len(body)] = body
            progs.append(p)
        self.sim, self.template = onira.build_onira(
            progs, config["mem_latency"])
        self._check_built()

    def _check_built(self) -> None:
        c, sim = self.config, self.sim
        kinds = {k.name: k for k in sim.kinds}
        params = sim.default_params()
        require_built("mem_latency", {c["mem_latency"]},
                      set(np.asarray(params.conn_latency).tolist()))
        require_built("flush_cycles", np.float32(c["flush_cycles"]),
                      np.float32(params.kind["cpu"]["flush_cycles"]))
        require_built("program_slots", c["program_slots"],
                      kinds["cpu"].init_state["prog"].shape[1])
        for key, kind in (("cpu_buffer", "cpu"), ("mem_buffer", "mem")):
            require_built(key, {c[key]}, set(kinds[kind].caps().ravel()
                                             .tolist()))
        require_built("time_dtype", c["time_dtype"],
                      str(self.template.time.dtype))

    def build_fn(self, inputs: dict):
        """A ``run_sweep`` build function returning the one built
        simulation; the programs are fixed by the configuration."""
        return lambda: (self.sim, self.template)

    @staticmethod
    def extract(sim, s) -> dict:
        """Per-point statistics of a host-side lane."""
        cs = s.comp_state["cpu"]
        return {"virtual_time": float(s.time),
                "epochs": int(s.stats.epochs),
                "ticks": int(s.stats.ticks),
                "progress_ticks": int(s.stats.progress_ticks),
                "delivered": int(s.stats.delivered),
                "retired": [int(x) for x in cs["retired"]],
                "halt_time": [float(x) for x in cs["halt_time"]],
                "done": [int(x) for x in cs["done"]]}
