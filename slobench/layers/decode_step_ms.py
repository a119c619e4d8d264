"""Mean device ms per execution of the decode step's program
(``jit_decode_fused``) in the trace."""
from slobench import tracefile


def read(run):
    if run.trace is None:
        return None
    ex = tracefile.executions(run.trace, "jit_decode_fused")
    return sum(e.dur for e in ex) / len(ex) / 1e6 if ex else None
