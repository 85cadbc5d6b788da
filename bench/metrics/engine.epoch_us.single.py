"""engine.epoch_us.single: microseconds of window per engine epoch,
the window's wall time over the epochs of its simulations (host clock
over the engine's own counter)."""


def read(run):
    epochs = sum(j["epochs"] for j in run.jobs if "sim_cycles" in j)
    return 1e6 * run.window_s / epochs if epochs else None
