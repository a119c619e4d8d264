"""Drive an ``InferenceServer`` open-loop and record what each layer did.

The loop is ``repro.serving.workloads.run_open_loop``'s, copied so that the
yardstick cannot move with the program: each request is submitted through
``InferenceServer.submit_request`` when its scheduled arrival passes, with
that arrival (not the submission) as its engine-clock arrival, so a late
generator shows as queueing; the server is pumped with ``step()`` in
between. The traffic runs ``lead_in_s`` before the measured window and keeps
arriving after it until every request that arrived in the window has its
first token, or ``lead_out_s`` has passed.

The loop calls ``probe.after_step()`` after each ``step()``
(``slobench.hooks.Probe``, which also times the layers below it).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax

from slobench import hooks

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class CompileCounter:
    """Counts XLA compiles and traced programs (jax.monitoring events). JAX's
    listeners cannot be removed, so one counter serves the whole process:
    take it from :func:`compile_counter`."""

    def __init__(self):
        self.compiles = 0
        self.traces = 0

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.compiles += 1
            elif event == TRACE_EVENT:
                self.traces += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


_COUNTER: list = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


@dataclasses.dataclass
class Window:
    """Engine-clock and host-clock bounds of the measured window."""
    w0: float = 0.0
    w1: float = 0.0
    host0: float = 0.0
    host1: float = 0.0
    stats0: Optional[dict] = None
    stats1: Optional[dict] = None


def _stats(core) -> dict:
    st = core.stats
    return {k: getattr(st, k) for k in (
        "iterations", "prefill_tokens", "decode_tokens", "device_busy_s",
        "evictions", "token_readbacks", "prefill_calls", "decode_calls")}


def run(server, probe: hooks.Probe, arrivals, limits, seconds: float,
        lead_in_s: float, lead_out_s: float, on_window=None,
        rid0: int = 0) -> dict:
    """Replay ``arrivals`` (``slobench.traffic.Arrival``, by offset) open
    loop. ``limits(arrival) -> (ttft_s, tbt_s, slo_class)``. ``on_window``
    is called with "start" and "end" at the window's edges (the profiler).
    Requests get ids from ``rid0`` on.
    Returns the request records, the window and the generator's lateness."""
    from repro.serving.request import Request

    core = server.core
    t0 = core.now()
    w = Window(w0=t0 + lead_in_s, w1=t0 + lead_in_s + seconds)
    handles: Dict[int, object] = {}
    meta: Dict[int, tuple] = {}
    late: List[float] = []
    in_window: List[int] = []
    i, phase = 0, "lead_in"
    stop_at = w.w1 + lead_out_s
    while True:
        now = core.now()
        while i < len(arrivals) and t0 + arrivals[i].offset <= now:
            a = arrivals[i]
            ttft, tbt, cls = limits(a)
            sched_t = t0 + a.offset
            rid = rid0 + i
            req = Request(rid=rid, arrival=sched_t, prompt_len=len(a.prompt),
                          max_output=a.max_output, ttft_slo=ttft,
                          tbt_slo=tbt, slo_class=cls)
            handles[rid] = server.submit_request(req, a.prompt)
            meta[rid] = (sched_t, ttft, tbt, a)
            late.append(now - sched_t)
            if w.w0 <= sched_t < w.w1:
                in_window.append(rid)
            i += 1
        if phase == "lead_in" and now >= w.w0:
            phase = "window"
            w.host0, w.stats0 = time.perf_counter(), _stats(core)
            if on_window:
                on_window("start")
        if phase == "window" and now >= w.w1:
            phase = "lead_out"
            w.host1, w.stats1 = time.perf_counter(), _stats(core)
            if on_window:
                on_window("end")
        if phase == "lead_out" and (now >= stop_at or all(
                handles[r].first_token_t is not None or handles[r].finished
                for r in in_window)):
            break
        if not core.has_work():
            nxt = t0 + arrivals[i].offset if i < len(arrivals) else stop_at
            edge = w.w0 if phase == "lead_in" else (
                w.w1 if phase == "window" else stop_at)
            time.sleep(max(min(nxt, edge) - core.now(), 0.0) + 1e-4)
            continue
        server.step()
        probe.after_step()
        if core.progress != "executed":
            time.sleep(1e-3)
    hooks.flush(server)
    return {"handles": handles, "meta": meta, "window": w, "late": late,
            "in_window": in_window, "t0": t0, "end": core.now()}
