"""The idle split over the engine's spans (``slobench/idle.py``) on a
synthetic trace and probe: the clock shift fitted to the paired dispatch
stamps, the innermost-span split that sums to the idle share, and nothing
read where the pairing fails or the program keeps no spans."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from slobench import idle, spec
from slobench.tracefile import Event, Trace

DEV = "/device:TPU:0"
SHIFT = 5_000_000                      # trace clock = host clock + SHIFT
T0, T1 = 10_000, 21_000                # traced window, host ns
# (name, start, end) on the host clock, ns: two rounds
ENGINE = [("engine.round", 10_000, 15_000),
          ("engine.schedule", 10_200, 10_600),
          ("engine.dispatch.chunk", 11_000, 14_000),
          ("engine.upload", 11_000, 11_500),
          ("engine.compile", 11_500, 14_000),
          ("engine.round", 15_000, 19_000),
          ("engine.schedule", 15_100, 15_400),
          ("engine.flush", 15_500, 17_000),
          ("engine.readback", 15_600, 16_800),
          ("engine.dispatch.decode", 17_000, 18_000),
          ("engine.upload", 17_000, 17_200),
          ("engine.launch", 17_200, 18_000)]
BUSY = [(10_000, 11_000), (14_000, 15_600), (16_800, 17_500),
        (18_000, 20_000)]                # host ns; idle after 20_000
STAMPS = [10_900, 16_900]              # the probe's dispatch stamps


def span(seq, name, start, end):
    from repro.serving.spans import Spans
    sp = Spans().span(name)
    sp.seq, sp.start_ns, sp.end_ns = seq, start, end
    return sp


def fake_run(stamps=STAMPS, traced=STAMPS, spans=True):
    ops = [Event("fusion", s + SHIFT, e - s, DEV) for s, e in BUSY]
    host = [Event(f"slobench.dispatch.{('chunk', 'decode')[i % 2]}",
                  t + SHIFT, 100) for i, t in enumerate(traced)]
    recs = [span(i, *s) for i, s in enumerate(ENGINE)]
    core = SimpleNamespace()
    if spans:
        core.spans = SimpleNamespace(records=lambda since_ns=None: [
            s for s in recs if since_ns is None or s.start_ns >= since_ns])
    probe = SimpleNamespace(core=core, dispatches=[
        (t / 1e9, "chunk", 1, 16, 1, None) for t in stamps])
    return SimpleNamespace(
        trace=Trace(ops, [], host, [DEV]), probe=probe,
        trace_t0=T0 / 1e9, trace_t1=T1 / 1e9, trace_window_s=(T1 - T0) / 1e9,
        window=SimpleNamespace(host0=T0 / 1e9, host1=T1 / 1e9))


def read(name, run):
    return spec.reader("layers", name)(run)


def test_shift_is_fitted_to_the_paired_differences():
    clock = idle.to_trace(fake_run())
    assert [clock(t) for t in (T0, 16_900, T1)] == [
        t + SHIFT for t in (T0, 16_900, T1)]
    run = fake_run(traced=[10_950, 16_970])   # 50 and 70 ns later
    clock = idle.to_trace(run)
    assert [clock(t) for t in STAMPS] == [10_950 + SHIFT, 16_970 + SHIFT]


@pytest.mark.parametrize("case", ["drift", "pause"])
def test_shift_follows_drift_and_passes_over_a_late_pair(case):
    stamps = [10_000_000 * i for i in range(1, 12)]
    if case == "drift":                        # the trace's clock runs 50 ppm fast
        traced = [t + t // 20_000 for t in stamps]
    else:                                      # one pair 4 ms late
        traced = [t + (4_000_000 if i == 5 else 0)
                  for i, t in enumerate(stamps)]
    run = fake_run(stamps=stamps, traced=traced)
    run.trace_t1 = stamps[-1] / 1e9
    clock = idle.to_trace(run)
    want = [t + SHIFT for t in traced]
    if case == "pause":
        want[5] -= 4_000_000
    assert [clock(t) for t in stamps] == want


def test_innermost_segments_of_nested_spans():
    segs = idle.innermost([(0, 10, "a"), (2, 5, "b"), (6, 8, "c"),
                           (12, 14, "d")])
    assert segs == [(0, 2, "a"), (2, 5, "b"), (5, 6, "a"), (6, 8, "c"),
                    (8, 10, "a"), (12, 14, "d")]


def test_idle_goes_to_the_innermost_span_and_sums_to_the_idle_share():
    run = fake_run()
    parts = idle.split(run)
    window = T1 - T0
    want = {"engine.upload": 500, "engine.compile": 2500,
            "engine.readback": 1200, "engine.launch": 500,
            idle.UNATTRIBUTED: 1000}
    assert parts == pytest.approx({k: 100.0 * v / window
                                   for k, v in want.items()})
    assert read("idle_compile_share", run) == pytest.approx(
        100 * 2500 / window)
    assert read("idle_upload_share.tbt", run) == pytest.approx(
        100 * 500 / window)
    assert read("idle_readback_share", run) == pytest.approx(
        100 * 1200 / window)
    assert read("idle_unattributed_share.tbt", run) == pytest.approx(
        100 * 1000 / window)
    assert sum(parts.values()) == pytest.approx(
        read("device_idle_share", run))
    # a span the device never idled under reads 0, not nothing
    assert idle.share(run, "engine.admit") == 0.0


def test_schedule_ms_is_the_mean_engine_schedule_span():
    assert read("schedule_ms", fake_run()) == pytest.approx(
        (400 + 300) / 2 / 1e6)


@pytest.mark.parametrize("case", ["count", "spread", "no_spans"])
def test_nothing_is_read_where_pairing_fails_or_no_spans(case):
    if case == "count":
        run = fake_run(traced=STAMPS + [18_500])
    elif case == "spread":                      # 2 ms apart in turn
        stamps = [10_000_000 * i for i in range(1, 12)]
        run = fake_run(stamps=stamps, traced=[
            t + 2_000_000 * (i % 2) for i, t in enumerate(stamps)])
        run.trace_t1 = stamps[-1] / 1e9
    else:
        run = fake_run(spans=False)
    assert idle.split(run) is None
    for name in ("idle_compile_share", "idle_upload_share",
                 "idle_readback_share", "idle_unattributed_share"):
        assert read(name, run) is None
    if case == "no_spans":
        assert read("schedule_ms", run) is None
    else:
        assert read("schedule_ms", run) is not None
