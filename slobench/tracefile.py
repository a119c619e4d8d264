"""Reduce a profiler trace (``.xplane.pb``) to device time per operation.

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds one event per operation that ran, named by its HLO text, and whose
line ``XLA Modules`` holds one event per execution of a compiled program.
Control-flow ops (``while``, ``conditional``, ``call``) span the ops of their
bodies and are dropped, so that no time is counted twice. An operation is attributed
to the program whose execution encloses it, so that two kernels of one name
in two programs stay apart. On the CPU the operations are events of the
host's plane that carry an ``hlo_module`` stat, which names their program.
Spans that the benchmark wrote on the host (``slobench.*``) are kept, to say
what the host was doing while the device was idle.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict
from typing import Dict, List, Optional

HOST_SPAN = "slobench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HLO_OP = re.compile(r"^(%?[^\s=]+) = .*?\s([a-z][a-z0-9\-]*)\(")
CONTAINERS = {"while", "conditional", "call"}


@dataclasses.dataclass
class Event:
    name: str
    start: int           # ns
    dur: int             # ns
    device: str = ""
    program: str = ""

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    programs: List[Event]
    host: List[Event]
    devices: List[str]


def program_name(name: str) -> str:
    """``jit_decode_fused(123)`` -> ``jit_decode_fused``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(hlo: str) -> tuple:
    """(short name, opcode) of an op event named by its HLO text:
    ``%fusion.3 = bf16[..] fusion(..), kind=kLoop`` -> (``%fusion.3 fusion``,
    ``fusion``); a Pallas kernel keeps its custom-call target in the name."""
    m = HLO_OP.match(hlo)
    if not m:
        return hlo[:80], ""
    short = f"{m.group(1)} {m.group(2)}"
    if "tpu_custom_call" in hlo:
        short += " tpu_custom_call"
    return short, m.group(2)


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, programs, host, devices = [], [], [], []
    cpu_ops = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        short, code = op_name(e.name)
                        if code not in CONTAINERS:
                            ops.append(Event(short, int(e.start_ns),
                                             int(e.duration_ns), plane.name))
                elif line.name == "XLA Modules":
                    programs += [Event(program_name(e.name), int(e.start_ns),
                                       int(e.duration_ns), plane.name)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN):
                        host.append(Event(e.name, int(e.start_ns),
                                          int(e.duration_ns)))
                        continue
                    st = _stats(e)
                    if "hlo_module" in st and e.duration_ns > 0:
                        cpu_ops.append(Event(
                            e.name, int(e.start_ns), int(e.duration_ns),
                            "/host:CPU", program_name(str(st["hlo_module"]))))
    if not devices and cpu_ops:
        devices, ops = ["/host:CPU"], cpu_ops
    attribute(ops, programs)
    return Trace(ops, programs, host, devices)


def find(directory: str) -> Optional[str]:
    paths = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    return paths[-1] if paths else None


def attribute(ops: List[Event], programs: List[Event]) -> None:
    """Set each op's program to the execution that encloses its start on the
    same device (ops that carry their program already keep it)."""
    by_dev: Dict[str, List[Event]] = defaultdict(list)
    for p in programs:
        by_dev[p.device].append(p)
    starts = {}
    for dev, ps in by_dev.items():
        ps.sort(key=lambda p: p.start)
        starts[dev] = [p.start for p in ps]
    for op in ops:
        ps = by_dev.get(op.device)
        if not ps:
            continue
        i = bisect.bisect_right(starts[op.device], op.start) - 1
        if i >= 0 and ps[i].start <= op.start < ps[i].end:
            op.program = ps[i].name


def busy_intervals(ops: List[Event], device: str) -> List[tuple]:
    """The union of the device's op intervals, merged, in ns."""
    iv = sorted((o.start, o.end) for o in ops if o.device == device)
    merged: List[list] = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not tr.devices:
        return 0.0
    tot = sum(e - s for d in tr.devices for s, e in busy_intervals(tr.ops, d))
    return tot / len(tr.devices) / 1e9


def top_ops(tr: Trace, k: int = 10) -> List[list]:
    """[[program/op, seconds]] of the k operations that took most time."""
    acc: Dict[str, int] = defaultdict(int)
    for o in tr.ops:
        acc[f"{o.program or '?'}/{o.name}"] += o.dur
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9 / max(len(tr.devices), 1)] for n, v in top]


def idle_by_host(tr: Trace, t0: int, t1: int, k: int = 10) -> List[list]:
    """[[host activity, seconds]]: the device's idle time in [t0, t1) on its
    first device, each gap split over the host spans that overlap it (the
    innermost span wins), the rest "other"."""
    if not tr.devices:
        return []
    busy = busy_intervals(tr.ops, tr.devices[0])
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    spans = sorted(tr.host, key=lambda h: (h.start, -h.dur))
    acc: Dict[str, int] = defaultdict(int)
    for gs, ge in gaps:
        covered = 0
        for h in spans:
            if h.start >= ge:
                break
            ov = min(h.end, ge) - max(h.start, gs)
            if ov > 0:
                acc[h.name] += ov
                covered += ov
        if ge - gs > covered:
            acc["other"] += ge - gs - covered
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in top]


def program_ops(tr: Trace, program: str, op_re: str) -> List[Event]:
    pat = re.compile(op_re)
    return [o for o in tr.ops if o.program == program and pat.search(o.name)]


def executions(tr: Trace, program: str) -> List[Event]:
    return [p for p in tr.programs if p.name == program]
