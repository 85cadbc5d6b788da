"""sim_cycles_per_s: simulated cycles of every simulation run in the
window, over the window's wall time (host clock; the window ends when
the last job that started in it ends)."""


def read(run):
    cycles = [j["sim_cycles"] for j in run.jobs if "sim_cycles" in j]
    return sum(cycles) / run.window_s if cycles else None
