"""End-to-end arithmetic on a synthetic event log, with and without a stall."""
from __future__ import annotations

import numpy as np

from slobench import measure

W0, W1 = 10.0, 40.0


def steady_log(stalls=()):
    """A request every 0.1 s from t=0 to 50, first token 0.2 s after
    arrival, then a token every 30 ms for 20 tokens. During a stall
    ``(at, seconds)`` nothing is emitted: a token due in it comes at its end,
    and every later one of that request shifts by its length."""
    reqs = []
    for i in range(500):
        a = 0.1 * i
        times = []
        for k in range(20):
            t = a + 0.2 + 0.03 * k
            for at, s in stalls:
                if t >= at:
                    t = at + s if t < at + s else t + s
            times.append(t)
        reqs.append(measure.Req(i, a, 1.0, 0.05, 20, 100, times))
    return reqs


def metrics(reqs):
    return (measure.nearest_rank(measure.ttfts(reqs, W0, W1, 60.0), .95),
            measure.nearest_rank(measure.tbt_gaps(reqs, W0, W1), .99),
            measure.goodput(reqs, W0, W1),
            measure.output_rate(reqs, W0, W1))


def test_steady_log_values():
    ttft, tbt, good, rate = metrics(steady_log())
    assert abs(ttft - 0.2) < 1e-9
    assert abs(tbt - 0.03) < 1e-9
    assert good == 300 / 30.0           # every window request met its limits
    assert abs(rate - 200.0) < 1.0      # 10 req/s x 20 tokens


def test_a_stall_moves_the_tails_and_goodput():
    base = metrics(steady_log())
    # a 0.4 s stall every 2 s: a few percent of the gaps and first tokens
    stalls = [(t, 0.4) for t in np.arange(11.0, 39.0, 2.0)]
    ttft, tbt, good, rate = metrics(steady_log(stalls))
    assert ttft > base[0] + 0.1
    assert tbt > base[1] + 0.1
    assert good < base[2] - 0.5


def test_unserved_requests_sit_in_the_tail():
    reqs = steady_log()
    for r in reqs[200:230]:             # 30 of the 300 window requests
        r.token_times = []
    v = measure.ttfts(reqs, W0, W1, 60.0)
    assert measure.unserved(reqs, W0, W1) == 30
    assert measure.nearest_rank(v, .95) > 30.0
    assert measure.goodput(reqs, W0, W1) == 270 / 30.0


def test_deadlines_after_the_window_are_not_judged():
    r = measure.Req(0, 39.9, 1.0, 0.05, 20, 10, [])
    assert measure.met_limits(r, W1)
    r = measure.Req(0, 30.0, 1.0, 0.05, 20, 10, [31.5])
    assert not measure.met_limits(r, W1)


def test_nearest_rank_and_support():
    v = list(range(1, 201))
    assert measure.nearest_rank(v, .95) == 190
    assert measure.beyond(200, .95) == 10
    assert measure.beyond(1000, .99) == 10


def test_split_metrics_are_read_by_their_base_reader():
    from types import SimpleNamespace

    from slobench import spec
    run = SimpleNamespace(reqs=steady_log(),
                          window=SimpleNamespace(w0=W0, w1=W1))
    assert abs(spec.reader("e2e", "tbt_p50_ms")(run) - 30.0) < 1e-6
    assert (spec.reader("layers", "mfu.tbt").__code__.co_filename
            == spec.reader("layers", "mfu").__code__.co_filename)
    assert abs(spec.reader("layers", "served_tok_s")(run)
               - measure.output_rate(run.reqs, W0, W1)) < 1e-9
