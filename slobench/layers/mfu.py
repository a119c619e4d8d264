"""Model FLOPs of the prefill and decode tokens dispatched in the window
(matrix products, attention over each token's context, the LM head of each
sampled row; ``slobench/counts.py``) over the window's seconds times the
chip's peak bf16 rate, in %."""
from slobench import counts


def read(run):
    w = run.window
    flops = sum(counts.step_flops(run.conf, kind, meta)
                for t, kind, _, _, _, meta in run.probe.dispatches
                if w.host0 <= t < w.host1)
    if not flops or not run.peak:
        return None
    return 100.0 * flops / ((w.host1 - w.host0) * run.peak["bf16_flops"])
