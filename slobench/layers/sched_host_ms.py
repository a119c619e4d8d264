"""Mean host ms per round spent inside the scheduler's ``schedule()`` in the
window, timed by the benchmark's wrapper around the call."""


def read(run):
    w = run.window
    v = [r.sched_s for r in run.probe.rounds if w.host0 <= r.t < w.host1]
    return 1e3 * sum(v) / len(v) if v else None
