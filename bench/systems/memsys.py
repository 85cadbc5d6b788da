"""The memsys configuration as the program under test builds and runs
it: ``repro.sims.memsys.build`` once, then every job's state is the
built template with that job's per-core inputs written in, run with the
configuration's L1 hit rate.

Every size of the configuration is either passed to the program's
builder or checked against what it built, so a configuration the
program does not build as stated is refused before any run."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.dse import apply_point
from repro.sims import memsys

from benchlib.harness import require_built


class System:
    def __init__(self, config: dict):
        self.config = config
        self.sim, _ = memsys.build(
            n_cores=config["cores"], pattern=config["pattern"],
            n_reqs=config["reads_per_core"],
            dram_latency=config["xbar_latency"])
        self.params = apply_point(
            self.sim.default_params(),
            {"kind.l1.extra_hit_rate": config["extra_hit_rate"]})
        self._check_built()

    def _check_built(self) -> None:
        c, sim = self.config, self.sim
        kinds = {k.name: k for k in sim.kinds}
        require_built("cores", c["cores"], kinds["core"].n_instances)
        require_built("reads_per_core", {c["reads_per_core"]}, set(
            np.asarray(kinds["core"].init_state["remaining"]).tolist()))
        require_built("l1_sets", c["l1_sets"],
                      kinds["l1"].init_state["tags"].shape[1])
        for key, kind in (("core_buffer", "core"), ("l1_buffer", "l1"),
                          ("dram_buffer", "dram")):
            require_built(key, {c[key]}, set(kinds[kind].caps().ravel()
                                             .tolist()))
        lat = np.asarray(self.params.conn_latency).tolist()
        require_built("link_latency and xbar_latency",
                      sorted([c["link_latency"]] * c["cores"]
                             + [c["xbar_latency"]]), sorted(lat))
        require_built("extra_hit_rate", np.float32(c["extra_hit_rate"]),
                      np.float32(self.params.kind["l1"]["extra_hit_rate"]))
        require_built("time_dtype", c["time_dtype"],
                      str(sim.init_state().time.dtype))

    def state(self, inputs: dict):
        """A fresh initial state holding ``inputs`` (think, seq, addr)."""
        st = self.sim.init_state()
        cs = dict(st.comp_state)
        cs["core"] = dict(cs["core"], **{
            k: jnp.asarray(np.asarray(inputs[k], np.int32))
            for k in ("think", "seq", "addr")})
        return dataclasses.replace(st, comp_state=cs)

    def run(self, state, until: float):
        return self.sim.run(state, until=until, params=self.params)

    def stats(self, out) -> dict:
        s = memsys.finish_stats(self.sim, out)
        s["progress_ticks"] = int(out.stats.progress_ticks)
        return s
