"""device.launches_per_point.sweep: programs launched on the device
(``XLA Modules`` events) in the traced window, per design point of the
campaigns (the program's ``sweep`` spans) that ran inside it."""
from benchlib import progtrace


def read(run):
    return progtrace.launches_per_point(run)
