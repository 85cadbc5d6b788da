"""rounds.lane_fill.sweep: per cent of dispatched lanes that carried a
live design point (``live`` over rung width, summed over the program's
``round.end`` events); the rest is padding."""


def read(run):
    width = sum(e["rung"] for e in run.rounds)
    return 100.0 * sum(e["live"] for e in run.rounds) / width \
        if width else None
