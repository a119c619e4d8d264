"""Roofline share of an attention kernel: the least time the chip needs
for the attention kernel's work in every traced execution of a step
program, over the kernel's device time there, in %.

The work is counted from the dispatches the host made between the
profiler's start and stop (no round is in flight at either edge, see
``slobench/run.py``); where their number differs from the program's
executions in the trace, the two cannot be paired and nothing is read."""
from slobench import counts, tracefile

KERNEL_OP = r"tpu_custom_call"      # the step's one Pallas kernel


def share(run, program: str, kind: str):
    if run.trace is None or not run.peak:
        return None
    ops = tracefile.program_ops(run.trace, program, KERNEL_OP)
    n = len(tracefile.executions(run.trace, program))
    calls = [d for d in run.probe.dispatches
             if d[1] == kind and run.trace_t0 <= d[0] <= run.trace_t1]
    if not ops or not n or len(calls) != n:
        return None
    layers = run.conf["num_hidden_layers"]
    least = 0.0
    for _, _, _, _, _, meta in calls:
        if kind == "decode":
            f, b = counts.decode_attention(run.conf, meta)
        else:
            f, b = counts.prefill_attention(run.conf, meta[0], meta[1])
        least += layers * counts.roofline_s(f, b, run.peak)
    return 100.0 * least / (sum(o.dur for o in ops) / 1e9)
