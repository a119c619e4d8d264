"""Host spans of the serving engine, for operators and profiles.

``with spans.span(name, **args) as sp:`` does three things:

* it opens a ``jax.profiler.TraceAnnotation(name)``, which costs next to
  nothing unless a profiler is recording, and otherwise puts the span on the
  profile's host timeline beside the device's operations, with the span's
  args as the event's stats (encoded only while a profiler records);
* it keeps a :class:`Span` (name, start and end on
  ``time.perf_counter_ns()``, the enclosing span, the engine round, args) in
  a buffer bounded at :data:`MAX_SPANS`, which drops the oldest span first;
* it adds the span's duration to per-name totals (count, total, longest).

The body may add args it learns while it runs (``sp.args["rids"] = ...``)
and read ``sp.dur_ns`` once the span has closed. A span stamped here lies on
a profile's clock after one constant shift: the profiler's origin.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional

import jax

# about 13 spans a serving round: minutes of serving at ~10 rounds a second
MAX_SPANS = 65536


class Span:
    """One span; its own context manager (``Spans.span`` makes it)."""
    __slots__ = ("seq", "name", "start_ns", "end_ns", "parent", "round",
                 "args", "_rec", "_ann")

    def __init__(self, rec: "Spans", name: str, args: dict):
        self._rec = rec
        self.name = name
        self.args = args
        self.seq = -1                 # sequence number, from 0, once opened
        self.parent = None            # seq of the innermost enclosing span
        self.round = rec.round
        self.start_ns = self.end_ns = 0
        self._ann = None

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns

    def __enter__(self) -> "Span":
        rec = self._rec
        self.seq = rec._seq
        rec._seq += 1
        if rec._open:
            self.parent = rec._open[-1]
        rec._open.append(self.seq)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        if self.args and self._ann.is_enabled():
            self._ann.set_metadata(**self.args)
        self._ann.__exit__(None, None, None)
        self._ann = None
        rec = self._rec
        rec._open.pop()
        rec._buf.append(self)
        dur = self.end_ns - self.start_ns
        tot = rec._totals.get(self.name)
        if tot is None:
            rec._totals[self.name] = [1, dur, dur]
        else:
            tot[0] += 1
            tot[1] += dur
            if dur > tot[2]:
                tot[2] = dur
        return False

    def __repr__(self) -> str:
        return (f"Span({self.seq}, {self.name!r}, parent={self.parent}, "
                f"round={self.round}, {self.dur_ns} ns, {self.args})")


class Spans:
    """The engine's span recorder (``EngineCore.spans``). ``round`` is the
    engine round that new spans belong to; the engine advances it."""

    def __init__(self):
        self.round = 0
        self._buf: collections.deque = collections.deque(maxlen=MAX_SPANS)
        self._open: List[int] = []
        self._seq = 0
        self._totals: Dict[str, List[int]] = {}   # name -> [count, ns, max]

    def span(self, name: str, **args) -> Span:
        """``with spans.span(name, **args) as sp:`` (see the module doc)."""
        return Span(self, name, args)

    def records(self, since_ns: Optional[int] = None) -> List[Span]:
        """The buffered spans in the order they closed (a child before its
        parent); with ``since_ns``, those that started at or after it."""
        spans = list(self._buf)
        if since_ns is None:
            return spans
        return [s for s in spans if s.start_ns >= since_ns]

    def totals(self) -> Dict[str, dict]:
        """Per span name since the engine was built (the buffer's bound does
        not apply): ``{"count", "total_ms", "max_ms"}``."""
        return {name: {"count": c, "total_ms": ns / 1e6, "max_ms": mx / 1e6}
                for name, (c, ns, mx) in list(self._totals.items())}
