"""Peak live KV pages over the pool's pages in the window, in %, sampled
after every engine round."""


def read(run):
    w = run.window
    v = [n for t, n in run.probe.live_pages if w.host0 <= t < w.host1]
    return 100.0 * max(v) / run.num_pages if v else None
