"""Share of the traced window, in %, in which no operation ran on the
device (1 - the union of op intervals over the window)."""
from slobench import tracefile


def read(run):
    if run.trace is None or not run.trace.ops or run.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - tracefile.busy_s(run.trace) / run.trace_window_s)
