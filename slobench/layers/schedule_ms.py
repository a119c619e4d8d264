"""Mean host ms of the engine's own ``engine.schedule`` spans (the
scheduler's ``schedule()`` call) that start in the window: the program's
twin of ``sched_host_ms``."""
from slobench import idle


def read(run):
    w = run.window
    h0, h1 = round(w.host0 * 1e9), round(w.host1 * 1e9)
    spans = idle.engine_spans(run, since_ns=h0)
    if spans is None:
        return None
    v = [s.dur_ns for s in spans
         if s.name == "engine.schedule" and s.start_ns < h1]
    return sum(v) / len(v) / 1e6 if v else None
