"""rounds.host_share.sweep: per cent of the window the round loop spent
in its own host work (assembly, harvest, compaction, refill: the sum of
``host_s`` over the program's ``round.end`` events)."""


def read(run):
    if not run.rounds:
        return None
    return 100.0 * sum(e["host_s"] for e in run.rounds) / run.window_s
