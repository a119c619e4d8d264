"""The device's idle time in the traced window, put to the engine's own spans.

The engine records its host phases as spans (``EngineCore.spans``,
``repro.serving.spans``) stamped with ``time.perf_counter_ns()``. The trace
keeps only the benchmark's own host spans (``tracefile.HOST_SPAN``), so the
engine's spans are read from the engine's buffer and moved onto the trace's
clock by a shift fitted to the benchmark's dispatch stamps (``hooks.Probe``,
host clock) paired in order with its ``slobench.dispatch.*`` spans in the
trace (``to_trace``).
Each idle instant of the first device in the traced window then goes to the
innermost engine span that covers it, or to "unattributed"; the parts sum to
``device_idle_share``. Where the program keeps no spans (no ``spans`` on the
engine) or the pairing fails, nothing is read.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Callable, Dict, List, Optional

from slobench import tracefile

DISPATCH_SPAN = "slobench.dispatch."
MAX_SPREAD_NS = 1_000_000     # quartiles of the paired differences, 1 ms
UNATTRIBUTED = "unattributed"


def engine_spans(run, since_ns: Optional[int] = None) -> Optional[list]:
    """The engine's buffered spans (those that started at or after
    ``since_ns``, host ns), or None where the program keeps none."""
    rec = getattr(run.probe.core, "spans", None)
    return rec.records(since_ns) if rec is not None else None


def to_trace(run) -> Optional[Callable[[int], int]]:
    """Host ``perf_counter`` ns -> trace ns, from the benchmark's dispatch
    stamps and its dispatch spans in the trace, paired in order. The trace
    clock is the host clock plus a line ``a + b * t`` fitted through the
    paired differences: ``b`` the median slope between pairs half the
    window apart, ``a`` the median of what is left. A constant shift has
    ``b`` = 0; the slope follows a profiler clock that drifts against
    ``perf_counter``, and the medians pass over a pair that a host pause
    between stamp and span pushed late. None where the counts differ or
    the differences' quartiles about the line lie more than
    ``MAX_SPREAD_NS`` apart."""
    if run.trace is None:
        return None
    traced = sorted(h.start for h in run.trace.host
                    if h.name.startswith(DISPATCH_SPAN))
    stamps = sorted(round(d[0] * 1e9) for d in run.probe.dispatches
                    if run.trace_t0 <= d[0] <= run.trace_t1)
    if not traced or len(traced) != len(stamps):
        return None
    diffs = [t - s for t, s in zip(traced, stamps)]
    h, s0 = len(diffs) // 2, stamps[0]
    slopes = [(diffs[i + h] - diffs[i]) / (stamps[i + h] - stamps[i])
              for i in range(len(diffs) - h) if stamps[i + h] > stamps[i]]
    b = statistics.median(slopes) if slopes else 0.0
    rest = [d - b * (s - s0) for d, s in zip(diffs, stamps)]
    a = statistics.median(rest)
    if len(rest) >= 2:
        q1, _, q3 = statistics.quantiles(rest, n=4)
        if q3 - q1 > MAX_SPREAD_NS:
            return None
    return lambda t: t + round(a + b * (t - s0))


def innermost(spans: List[tuple]) -> List[tuple]:
    """[(start, end, name)] of nested spans -> disjoint, sorted segments,
    each named by the innermost span that covers it."""
    segs: List[tuple] = []
    stack: List[tuple] = []       # (end, name), innermost last
    cur = None
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, nm = stack.pop()
            if end > cur:
                segs.append((cur, end, nm))
                cur = end
        if stack and s > cur:
            segs.append((cur, s, stack[-1][1]))
        if stack:
            e = min(e, stack[-1][0])
        stack.append((e, name))
        cur = s
    while stack:
        end, nm = stack.pop()
        if end > cur:
            segs.append((cur, end, nm))
            cur = end
    return segs


def split_gaps(busy: List[tuple], spans: List[tuple], t0: int,
               t1: int) -> Dict[str, int]:
    """ns of idle time in [t0, t1) (outside the merged ``busy`` intervals)
    per innermost span name, the rest under ``UNATTRIBUTED``."""
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1))
    segs = innermost(spans)
    acc: Dict[str, int] = defaultdict(int)
    j = 0
    for gs, ge in gaps:
        if ge <= gs:
            continue
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        covered, k = 0, j
        while k < len(segs) and segs[k][0] < ge:
            ov = min(segs[k][1], ge) - max(segs[k][0], gs)
            if ov > 0:
                acc[segs[k][2]] += ov
                covered += ov
            k += 1
        acc[UNATTRIBUTED] += ge - gs - covered
    return dict(acc)


def split(run) -> Optional[Dict[str, float]]:
    """{engine span name or ``UNATTRIBUTED``: % of the traced window in
    which the first device idled under it}; None where nothing can be read.
    Computed once per run."""
    if "_engine_idle" not in vars(run):
        run._engine_idle = _split(run)
    return run._engine_idle


def _split(run) -> Optional[Dict[str, float]]:
    if run.trace is None or not run.trace.devices or run.trace_window_s <= 0:
        return None
    records = engine_spans(run)
    clock = to_trace(run)
    if records is None or clock is None:
        return None
    h0, h1 = round(run.trace_t0 * 1e9), round(run.trace_t1 * 1e9)
    spans = [(clock(s.start_ns), clock(s.end_ns), s.name) for s in records
             if s.end_ns > h0 and s.start_ns < h1]
    busy = tracefile.busy_intervals(run.trace.ops, run.trace.devices[0])
    idle = split_gaps(busy, spans, clock(h0), clock(h1))
    window = h1 - h0
    return {name: 100.0 * ns / window for name, ns in idle.items()}


def share(run, name: str) -> Optional[float]:
    """% of the traced window the device idled under engine span ``name``
    (0 where it never did); None where nothing can be read."""
    parts = split(run)
    return None if parts is None else parts.get(name, 0.0)
