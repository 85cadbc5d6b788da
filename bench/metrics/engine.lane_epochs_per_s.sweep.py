"""engine.lane_epochs_per_s.sweep: engine epochs advanced by all live
lanes per second of round time (``epochs`` over ``dur`` of the
program's ``round.end`` events): the batched epoch loop's rate."""


def read(run):
    dur = sum(e["dur"] for e in run.rounds)
    return sum(e["epochs"] for e in run.rounds) / dur if dur else None
