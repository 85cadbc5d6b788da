"""sweep.rows.idle_share.sweep: per cent of the traced window in which
the device was idle while the program's innermost span was
``sweep.transfer`` or ``sweep.extract`` (``run_sweep`` pulling the final
states to the host and turning each lane into a result row)."""
from benchlib import progtrace


def read(run):
    return progtrace.idle_share(run, ("sweep.transfer", "sweep.extract"))
