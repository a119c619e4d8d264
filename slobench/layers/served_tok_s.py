"""Output tokens emitted in the window per second of the window: the served
rate, read per layer in cells whose rate swings too widely to be bounded."""
from slobench import measure


def read(run):
    return measure.output_rate(run.reqs, run.window.w0, run.window.w1)
