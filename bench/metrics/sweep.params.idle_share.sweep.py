"""sweep.params.idle_share.sweep: per cent of the traced window in which
the device was idle while the program's innermost span was
``sweep.params`` (``run_sweep`` building the campaign's parameter
batch: ``apply_point`` per point, then ``stack_params``)."""
from benchlib import progtrace


def read(run):
    return progtrace.idle_share(run, ("sweep.params",))
