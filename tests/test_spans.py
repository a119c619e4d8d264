"""The engine's span recorder (``repro.serving.spans``): nesting, the bounded
buffer, per-name totals, the span tree a paged round writes, and the clock
the spans share with a JAX profile."""
from __future__ import annotations

import glob

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SlidingServeScheduler
from repro.serving import spans as spans_mod
from repro.serving.engine import ServingEngine
from repro.serving.request import Request
from repro.serving.spans import Spans

ROUND_CHILDREN = {"engine.admit", "engine.schedule", "engine.grow",
                  "engine.assemble", "engine.flush", "engine.dispatch.chunk",
                  "engine.dispatch.decode"}


def test_spans_nest_record_parents_and_sum_totals():
    rec = Spans()
    rec.round = 7
    with rec.span("outer", a=1) as outer:
        with rec.span("inner") as inner:
            inner.args["late"] = "set inside"
        with rec.span("inner"):
            pass
    with rec.span("outer"):
        pass
    got = rec.records()
    # buffered as they close: children before their parent
    assert [s.name for s in got] == ["inner", "inner", "outer", "outer"]
    assert [s.parent for s in got] == [outer.seq, outer.seq, None, None]
    assert outer.args == {"a": 1} and inner.args == {"late": "set inside"}
    assert all(s.round == 7 for s in got)
    assert all(0 <= s.dur_ns for s in got)
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    tot = rec.totals()
    assert tot["inner"]["count"] == 2 and tot["outer"]["count"] == 2
    for name in ("inner", "outer"):
        durs = [s.dur_ns / 1e6 for s in got if s.name == name]
        assert tot[name]["total_ms"] == pytest.approx(sum(durs))
        assert tot[name]["max_ms"] == pytest.approx(max(durs))
    assert rec.records(since_ns=got[-1].start_ns) == [got[-1]]


def test_span_closes_when_its_body_raises():
    rec = Spans()
    with pytest.raises(ValueError):
        with rec.span("failing"):
            raise ValueError("boom")
    with rec.span("next"):
        pass
    assert [(s.name, s.parent) for s in rec.records()] == [
        ("failing", None), ("next", None)]


def test_span_buffer_drops_the_oldest_but_totals_keep_counting(monkeypatch):
    monkeypatch.setattr(spans_mod, "MAX_SPANS", 3)
    rec = Spans()
    for i in range(5):
        with rec.span("s", i=i):
            pass
    assert [s.args["i"] for s in rec.records()] == [2, 3, 4]
    assert [s.seq for s in rec.records()] == [2, 3, 4]
    assert rec.totals()["s"]["count"] == 5


# ---------------------------------------------------------------------------
# a tiny paged engine: one serve to compile, one more under the profiler
# ---------------------------------------------------------------------------
def _requests(rng, cfg, rid0):
    reqs = [Request(rid=rid0 + i, arrival=0.0,
                    prompt_len=int(rng.integers(16, 40)), max_output=4,
                    ttft_slo=30.0, tbt_slo=5.0) for i in range(3)]
    prompts = {r.rid: rng.integers(1, cfg.vocab_size,
                                   r.prompt_len).astype(np.int32)
               for r in reqs}
    return reqs, prompts


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    import jax
    cfg = get_config("llama3.2-3b").smoke()
    reqs, prompts = _requests(np.random.default_rng(3), cfg, 0)
    eng = ServingEngine(cfg, SlidingServeScheduler(max_budget=256,
                                                   max_iter_time=5.0),
                        cache_mode="paged", kv_capacity_tokens=2048)
    first = eng.serve(reqs, {k: v.copy() for k, v in prompts.items()})
    n_first = len(eng.spans.records())
    again = [Request(rid=r.rid + 100, arrival=0.0, prompt_len=r.prompt_len,
                     max_output=r.max_output, ttft_slo=30.0, tbt_slo=5.0)
             for r in reqs]
    trace_dir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        eng.serve(again, {r.rid + 100: prompts[r.rid].copy() for r in reqs})
    finally:
        jax.profiler.stop_trace()
    recs = eng.spans.records()
    xplane = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                              recursive=True))[-1]
    return dict(cfg=cfg, eng=eng, first=first, prompts=prompts, reqs=reqs,
                spans_first=recs[:n_first], spans_traced=recs[n_first:],
                xplane=xplane)


def test_paged_round_writes_the_span_tree(served):
    recs = served["spans_first"] + served["spans_traced"]
    by_seq = {s.seq: s for s in recs}
    rounds = [s for s in recs if s.name == "engine.round"]
    assert rounds and all(s.parent is None for s in rounds)
    assert [s.args["round"] for s in rounds] == [s.round for s in rounds]
    assert all({"live_pages", "queued", "active"} <= set(s.args)
               for s in rounds)
    for s in recs:
        parent = by_seq[s.parent].name if s.parent is not None else None
        if s.name in ROUND_CHILDREN:
            assert parent == "engine.round", (s, parent)
        elif s.name == "engine.readback":
            assert parent == "engine.flush"
        elif s.name in ("engine.upload", "engine.compile", "engine.launch"):
            assert parent.startswith("engine.dispatch."), (s, parent)
        else:
            assert s.name == "engine.round", s
        assert s.round == by_seq.get(s.parent, s).round
    admitted = [rid for s in recs if s.name == "engine.admit"
                for rid in s.args["rids"]]
    assert sorted(admitted) == [0, 1, 2, 100, 101, 102]
    for s in recs:
        if s.name.startswith("engine.dispatch."):
            kids = [c.name for c in recs if c.parent == s.seq]
            assert kids[0] == "engine.upload" and len(kids) == 2
            assert kids[1] in ("engine.compile", "engine.launch")
            assert 0 < s.args["rows"] <= s.args["Rb"]
            assert s.args["tokens"] >= s.args["rows"]


def test_first_call_of_a_shape_is_compile_then_launch(served):
    recs = sorted(served["spans_first"] + served["spans_traced"],
                  key=lambda s: s.seq)
    seen, kinds = set(), []
    for s in recs:
        if not s.name.startswith("engine.dispatch."):
            continue
        kind = s.name.rsplit(".", 1)[1]
        key = ((kind, s.args["Rb"], s.args["nb"]) if kind == "decode"
               else (kind, s.args["Rb"], s.args["Lb"], s.args["nb"]))
        call = next(c for c in recs
                    if c.parent == s.seq and c.name != "engine.upload")
        assert call.name == ("engine.launch" if key in seen
                             else "engine.compile"), (key, call)
        if call.name == "engine.compile":
            assert call.args["key"] == key
        seen.add(key)
        kinds.append(call.name)
    assert seen == served["eng"]._seen_shapes
    assert "engine.compile" in kinds and "engine.launch" in kinds
    st = served["eng"].stats
    assert st.token_readbacks == st.iterations


def test_spans_leave_greedy_tokens_unchanged(served):
    cfg, reqs, prompts = served["cfg"], served["reqs"], served["prompts"]
    ref = ServingEngine(cfg, SlidingServeScheduler(max_budget=256,
                                                   max_iter_time=5.0),
                        cache_mode="slot", max_slots=4, max_len=128)
    out = ref.serve([Request(rid=r.rid, arrival=0.0, prompt_len=r.prompt_len,
                             max_output=r.max_output, ttft_slo=30.0,
                             tbt_slo=5.0) for r in reqs],
                    {k: v.copy() for k, v in prompts.items()})
    assert served["first"]["outputs"] == out["outputs"]
    traced = {rid - 100: toks for rid, toks
              in served["eng"]._tokens_out.items() if rid >= 100}
    assert traced == out["outputs"]


def test_spans_lie_on_the_profile_clock_after_one_shift(served):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(served["xplane"])
    traced = sorted((int(e.start_ns), e.name) for plane in pd.planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith("engine."))
    recs = sorted((s.start_ns, s.name) for s in served["spans_traced"])
    assert len(recs) > 20
    assert [n for _, n in traced] == [n for _, n in recs]
    diffs = [t - s for (t, _), (s, _) in zip(traced, recs)]
    assert max(diffs) - min(diffs) < 200_000        # 0.2 ms


def test_span_args_reach_the_profile_as_stats(served):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(served["xplane"])
    traced = sorted((int(e.start_ns), e.name,
                     {k: str(v) for k, v in e.stats})
                    for plane in pd.planes if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith("engine."))
    recs = sorted(served["spans_traced"], key=lambda s: s.start_ns)
    assert [(n, st) for _, n, st in traced] == [
        (s.name, {k: str(v) for k, v in s.args.items()}) for s in recs]
    assert {"rids": "[100, 101, 102]"} in [st for _, _, st in traced]
