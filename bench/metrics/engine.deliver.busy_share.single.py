"""engine.deliver.busy_share.single: per cent of the device busy time in
the traced window taken by ops of the engine's ``engine.deliver`` scope
(the crossbar's arbitration and delivery of each epoch)."""
from benchlib import progtrace


def read(run):
    return progtrace.busy_share(run, "engine.deliver")
