"""Roofline share of an attention kernel: the least time the chip needs
for the kernel's work in every traced execution of a step program, over
the kernel's device time there, in %.

The kernel is found by its name in the trace (a pattern over the op's
short name, ``tracefile.op_name``), so that another Pallas kernel in the
same step is not counted as its time. Its work is the family's
``attention`` for the whole step (``spec.family``), summed over the
dispatches the host made between the profiler's start and stop (no round
is in flight at either edge, see ``slobench/run.py``); where their number
differs from the program's executions in the trace, the two cannot be
paired and nothing is read."""
from slobench import spec, tracefile


def roofline_s(flops: int, nbytes: int, peak: dict) -> float:
    """Least seconds the chip needs for the work: the larger of compute and
    memory time at the device's peaks."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_s"])


def share(run, program: str, kind: str, kernel: str):
    if run.trace is None or not run.peak:
        return None
    ops = tracefile.program_ops(run.trace, program, kernel)
    n = len(tracefile.executions(run.trace, program))
    calls = [d for d in run.probe.dispatches
             if d[1] == kind and run.trace_t0 <= d[0] <= run.trace_t1]
    if not ops or not n or len(calls) != n:
        return None
    attention = spec.family(run.conf).attention
    least = sum(roofline_s(*attention(run.conf, kind, meta), run.peak)
                for _, _, _, _, _, meta in calls)
    return 100.0 * least / (sum(o.dur for o in ops) / 1e9)
