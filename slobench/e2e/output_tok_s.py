"""Output tokens emitted in the window, per second of the window."""
from slobench import measure


def read(run):
    return measure.output_rate(run.reqs, run.window.w0, run.window.w1)
