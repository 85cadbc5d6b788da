"""engine.cycles_per_epoch.single: simulated cycles per engine epoch,
how far Smart Ticking jumps on average (the engine's counters; moves
only when ticking changes)."""


def read(run):
    jobs = [j for j in run.jobs if "sim_cycles" in j]
    epochs = sum(j["epochs"] for j in jobs)
    return sum(j["sim_cycles"] for j in jobs) / epochs if epochs else None
