"""End-to-end arithmetic over the requests of one run.

Times are on the engine's clock (seconds). A request's record holds its
scheduled arrival, its limits, and the times of its output tokens as the
client saw them (the engine's token events). Only requests that arrived in
the window ``[w0, w1)`` count, and of token gaps only those whose later token
was emitted in the window. A request with no first token when observation
ends (``end``) is unserved, with ``end - arrival`` as its time to first
token: a lower bound that still sits above the requests that were served.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence


@dataclasses.dataclass
class Req:
    rid: int
    arrival: float
    ttft_limit: float
    tbt_limit: float
    max_output: int
    prompt_len: int
    token_times: List[float]
    evictions: int = 0
    finished: bool = False


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The q-quantile by nearest rank: the smallest value with at least a
    share q of the values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    return v[max(int(math.ceil(q * len(v))) - 1, 0)]


def beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-quantile."""
    return n - max(int(math.ceil(q * n)), 1)


def in_window(reqs: Sequence[Req], w0: float, w1: float) -> List[Req]:
    return [r for r in reqs if w0 <= r.arrival < w1]


def ttfts(reqs: Sequence[Req], w0: float, w1: float, end: float) -> List[float]:
    return [(r.token_times[0] if r.token_times else end) - r.arrival
            for r in in_window(reqs, w0, w1)]


def unserved(reqs: Sequence[Req], w0: float, w1: float) -> int:
    """Window requests with no first token when observation ended."""
    return sum(1 for r in in_window(reqs, w0, w1) if not r.token_times)


def tbt_gaps(reqs: Sequence[Req], w0: float, w1: float) -> List[float]:
    out = []
    for r in reqs:
        t = r.token_times
        out += [t[k] - t[k - 1] for k in range(1, len(t)) if w0 <= t[k] < w1]
    return out


def met_limits(r: Req, w1: float) -> bool:
    """Whether r met every deadline that falls before w1: its first token by
    ``arrival + ttft_limit``, its k-th by ``arrival + ttft_limit +
    (k - 1) * tbt_limit``."""
    for k in range(1, r.max_output + 1):
        due = r.arrival + r.ttft_limit + (k - 1) * r.tbt_limit
        if due >= w1:
            return True
        if len(r.token_times) < k or r.token_times[k - 1] > due:
            return False
    return True


def goodput(reqs: Sequence[Req], w0: float, w1: float) -> float:
    """Requests that arrived in the window and met both limits, per second."""
    return sum(met_limits(r, w1) for r in in_window(reqs, w0, w1)) / (w1 - w0)


def output_rate(reqs: Sequence[Req], w0: float, w1: float) -> float:
    """Output tokens emitted in the window, per second."""
    return sum(1 for r in reqs for t in r.token_times
               if w0 <= t < w1) / (w1 - w0)


def from_events(events, meta: dict) -> List[Req]:
    """Request records from the server's event log; ``meta`` maps rid to
    ``(scheduled arrival, ttft_limit, tbt_limit, arrival)``."""
    from repro.serving.engine import EventKind
    recs = {rid: Req(rid, m[0], m[1], m[2], m[3].max_output,
                     len(m[3].prompt), [])
            for rid, m in meta.items()}
    for ev in events:
        r = recs.get(ev.rid)
        if r is None:
            continue
        if ev.kind in (EventKind.FIRST_TOKEN, EventKind.TOKEN):
            r.token_times.append(ev.t)
        elif ev.kind is EventKind.EVICTED:
            r.evictions += 1
        elif ev.kind is EventKind.FINISHED:
            r.finished = True
    return list(recs.values())
