"""engine.tick.busy_share.single: per cent of the device busy time in
the traced window taken by ops of the engine's ``engine.tick.<kind>``
scopes (every component kind's tick of each epoch)."""
from benchlib import progtrace


def read(run):
    return progtrace.busy_share(run, "engine.tick")
