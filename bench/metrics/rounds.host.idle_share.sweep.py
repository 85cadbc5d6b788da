"""rounds.host.idle_share.sweep: per cent of the traced window in which
the device was idle while the program's innermost span was one of the
round loop's own host phases: ``round.assemble``, ``round.launch``,
``round.harvest`` or ``rounds.final`` (idle under ``round.wait``, the
liveness wait, is not counted)."""
from benchlib import progtrace


def read(run):
    return progtrace.idle_share(run, ("round.assemble", "round.launch",
                                      "round.harvest", "rounds.final"))
