"""Median gap, in ms, between consecutive output tokens of a request, over
every gap whose later token was emitted in the window: the pace at which a
user sees tokens arrive."""
from slobench import measure


def read(run):
    gaps = measure.tbt_gaps(run.reqs, run.window.w0, run.window.w1)
    return 1e3 * measure.nearest_rank(gaps, 0.5) if gaps else None
