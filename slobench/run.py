"""Run one cell of the benchmark once.

    python3 -m slobench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix. The run makes the weights of the
configuration's model family (``slobench/configs/<family>.py``) from the
seed on the chip, builds the serving engine as ``launch/serve.py``
does (``SlidingServeScheduler``, paged KV, prefix cache, overlapped rounds,
greedy, no speculation) behind ``InferenceServer``, compiles or loads every
step shape the mix lists, replays the mix open-loop through a lead-in, the
measured window of ``--seconds`` and a lead-out, reads the device's peak
memory, frees the engine, and checks a sample of the served tokens against
the configuration's plain reference (``slobench/check.py``).

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds its
per-layer metrics. Lines before the last are labels; a ``"phase"`` label
is printed, and flushed, as each phase ends (start, weights, build, warm,
lead_in, window, lead_out, trace_load with ``--trace 1``, read, check),
with its seconds, so that a run stopped at a time limit shows where it
stood. The last line of standard output is the result, and the numbers
compared for ``correct`` are also the last lines of standard error.
Without a TPU, with fewer chips than the cell asks for, or outside a
checkout of the repository, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

from slobench import spec  # noqa: E402

CACHE_DIR = spec.CHECKOUT / ".jax_cache"
TRACE_DIR = spec.CHECKOUT / ".slobench_trace"


class Refused(Exception):
    """The run cannot be made here (no chip, no program, bad arguments)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    mix: dict
    e2e: list            # metric entries of BENCHMARK.json
    per_layer: list

    @classmethod
    def load(cls, name: str) -> "Cell":
        bench = spec.load_benchmark()
        entry = spec.find(bench["workloads"], name, "workload")
        return cls(name, entry["chips"],
                   spec.load_config_by_name(entry["config"], bench),
                   spec.load_traffic(entry["traffic"]),
                   spec.metrics_for(bench, name, "end_to_end"),
                   spec.metrics_for(bench, name, "per_layer"))


@dataclasses.dataclass
class RunData:
    """What the metric readers read (``slobench/e2e``, ``slobench/layers``)."""
    conf: dict
    reqs: list
    window: object       # openloop.Window
    end: float
    seconds: float
    setup_s: float
    probe: object        # hooks.Probe
    num_pages: int
    peak: dict
    trace: object = None  # tracefile.Trace
    trace_t0: float = 0.0  # host clock as the profiler started and stopped
    trace_t1: float = 0.0

    @property
    def trace_window_s(self) -> float:
        return self.trace_t1 - self.trace_t0


def label(dev: dict, name: str, **fields) -> None:
    print(json.dumps({"label": name, "device": [dev["platform"], dev["kind"],
                                                dev["count"]], **fields},
                     default=float), flush=True)


def phase(dev: dict, name: str, t0: float, **fields) -> float:
    """Print the label of the phase ``name`` that began at ``t0`` and ends
    now, with its seconds, so that a run stopped from outside shows how
    far it came; returns now."""
    now = time.perf_counter()
    label(dev, "phase", phase=name, seconds=now - t0, **fields)
    return now


def require_chip(chips: int, need_chip: bool = True):
    """The JAX devices the cell runs on; Refused where they are missing."""
    if not (spec.CHECKOUT / "src" / "repro").is_dir():
        raise Refused(f"no src/repro in {spec.CHECKOUT}: not a checkout")
    src = str(spec.CHECKOUT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    devs = jax.devices()
    if need_chip and devs[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def configure_jax() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes(devices) -> Optional[int]:
    vals = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             need_chip: bool = True, persistent_cache: bool = True,
             control: bool = False) -> dict:
    """One run of ``cell``; returns the result object (see module doc).
    ``need_chip=False`` and ``persistent_cache=False`` let a test drive the
    rest of a run on the CPU. ``control=True`` puts the precision control in
    the program's place for the check (``check.gaps``: the tokens that the
    reference computed in float8 puts first), which must come out not
    correct; the program's own gap is then kept as ``program_gap``."""
    devices = require_chip(cell.chips, need_chip)
    if persistent_cache:
        configure_jax()
    import jax

    from slobench import (check, hooks, measure, openloop, traffic, tracefile,
                          weights)
    d0 = devices[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind,
           "count": len(jax.devices())}
    peaks = json.loads((spec.HERE / "peaks.json").read_text())
    if d0.platform == "tpu" and d0.device_kind not in peaks:
        raise Refused(f"no peaks for device kind {d0.device_kind!r}")
    peak = peaks.get(d0.device_kind, {})
    counter = openloop.compile_counter()

    t = phase(dev, "start", T_START)
    w, params = weights.make(cell.conf, seed)
    cfg = spec.family(cell.conf).model_config(cell.conf)
    weights.check_layout(cfg, params)
    jax.block_until_ready(params)
    t = phase(dev, "weights", t)
    server = hooks.build_server(cell.conf, cfg, params)
    core = server.core
    t = phase(dev, "build", t)
    n_warm = hooks.warm(core, cell.mix["warm"])
    t = phase(dev, "warm", t, shapes=n_warm)
    arrivals = traffic.make(cell.mix, seconds, seed, cell.conf["vocab_size"])
    probe = hooks.Probe(server, annotate=trace)
    c_setup = (counter.compiles, counter.traces)
    label(dev, "setup", compiles=c_setup[0], traced=c_setup[1],
          kv_pool_tokens=cell.conf["kv_pool_tokens"], cache_dir=str(CACHE_DIR))

    marks = {}

    def on_window(edge):
        marks[edge] = (time.perf_counter(), counter.compiles, counter.traces)
        if edge == "start":
            phase(dev, "lead_in", marks["loop"])
        else:
            phase(dev, "window", marks["start"][0])
        if not trace:
            return
        # no round is in flight at either edge, so the trace holds the
        # execution of exactly the steps dispatched between the two marks
        jax.block_until_ready(core.cache)
        if edge == "start":
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            marks["trace0"] = time.perf_counter()
        else:
            marks["trace1"] = time.perf_counter()
            jax.profiler.stop_trace()
            marks["trace_stop_s"] = time.perf_counter() - marks["trace1"]

    marks["loop"] = time.perf_counter()
    res = openloop.run(server, probe, arrivals,
                     lambda a: traffic.limits(cell.mix, a), seconds,
                     cell.mix["lead_in_s"], cell.mix["lead_out_s"], on_window)
    phase(dev, "lead_out", marks["end"][0],
          trace_stop_s=marks.get("trace_stop_s"))
    win = res["window"]
    setup_s = marks["start"][0] - T_START
    reqs = measure.from_events(server.events, res["meta"])
    data = RunData(cell.conf, reqs, win, res["end"], seconds, setup_s, probe,
                   core.alloc.num_blocks, peak)
    if trace:
        t = time.perf_counter()
        path = tracefile.find(str(TRACE_DIR))
        data.trace = tracefile.load(path) if path else None
        data.trace_t0, data.trace_t1 = marks["trace0"], marks["trace1"]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        phase(dev, "trace_load", t, ops=len(data.trace.ops) if data.trace
              else None)

    w0, w1 = win.w0, win.w1
    ttft = measure.ttfts(reqs, w0, w1, res["end"])
    gaps = measure.tbt_gaps(reqs, w0, w1)
    attempted = len(ttft)
    # failed: refused or aborted by the server; a request merely still
    # queued when observation ends is "unserved" (and sits in the TTFT tail)
    n_failed = sum(1 for rid in res["in_window"] if res["handles"][rid].aborted)
    met = sum(measure.met_limits(r, w1) for r in measure.in_window(reqs, w0, w1))
    label(dev, "window", seconds=seconds, requests=attempted,
          failed=n_failed, unserved=measure.unserved(reqs, w0, w1),
          met_limits=met,
          compiles=marks["end"][1] - marks["start"][1],
          traced=marks["end"][2] - marks["start"][2],
          generator_late_mean_ms=1e3 * sum(res["late"]) / max(len(res["late"]), 1),
          generator_late_max_ms=1e3 * max(res["late"], default=0.0),
          **{k: win.stats1[k] - win.stats0[k] for k in (
              "evictions", "iterations", "prefill_tokens", "decode_tokens")})
    if ttft and gaps:
        label(dev, "tails", ttft_p50_ms=1e3 * measure.nearest_rank(ttft, .5),
              ttft_p90_ms=1e3 * measure.nearest_rank(ttft, .9),
              ttft_p95_ms=1e3 * measure.nearest_rank(ttft, .95),
              ttft_beyond_p95=measure.beyond(len(ttft), .95),
              tbt_p50_ms=1e3 * measure.nearest_rank(gaps, .5),
              tbt_p99_ms=1e3 * measure.nearest_rank(gaps, .99),
              tbt_gaps=len(gaps), tbt_beyond_p99=measure.beyond(len(gaps), .99),
              goodput_rps=measure.goodput(reqs, w0, w1), attainment=met / max(attempted, 1),
              output_tok_s=measure.output_rate(reqs, w0, w1))
    warmed = {("decode", rb, nb) for rb, nb in cell.mix["warm"].get("decode", [])}
    warmed |= {("chunk", rb, lb, nb)
               for rb, lb, nb in cell.mix["warm"].get("chunk", [])}
    seen = hooks.seen_shapes(core)
    label(dev, "shapes", seen=len(seen), unwarmed=sorted(seen - warmed))

    t = time.perf_counter()
    entries = cell.per_layer if trace else cell.e2e
    kind = "layers" if trace else "e2e"
    metrics = {}
    for m in entries:
        v = spec.reader(kind, m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {**dev, "memory_peak_bytes": peak_bytes(devices)}
    result = {"correct": False, "attempted": attempted, "failed": n_failed,
              "metrics": metrics, "device": device}
    if trace and data.trace is not None:
        tr = data.trace
        device["busy_s"] = tracefile.busy_s(tr)
        device["window_s"] = data.trace_window_s
        ev = tr.ops + tr.host
        lo = min((e.start for e in ev), default=0)
        hi = max((e.end for e in ev), default=0)
        result["breakdown"] = {"device_ops": tracefile.top_ops(tr),
                               "idle_gaps": tracefile.idle_by_host(tr, lo, hi)}
    phase(dev, "read", t, metrics=len(metrics))

    # ---- correctness, after the window, with the engine's state freed ----
    evicted = {r.rid: r.evictions for r in reqs}
    finished = [(res["meta"][rid][3].prompt, list(h.collected), evicted[rid])
                for rid, h in sorted(res["handles"].items())
                if h.finished and not h.aborted]
    hooks.free(core)                   # the KV pool, before anything else
    del server, core, probe, data, res, on_window
    gc.collect()
    sample = check.pick(finished, seed)
    t = time.perf_counter()
    prog, ctrl = check.gaps(cell.conf, w, sample, control)
    gap, n_tok = check.widest(ctrl if control else prog), len(prog)
    if control:
        result["program_gap"] = check.widest(prog)
    limit = cell.conf["check"]["max_logit_gap"]
    least = cell.conf["check"]["min_tokens"]
    checks = {"logit_gap": {"value": gap, "limit": limit},
              "tokens_compared": {"value": n_tok, "limit": least}}
    result["correct"] = bool(gap <= limit and n_tok >= least)
    phase(dev, "check", t, requests=len(sample),
          longest=max((len(p) + len(o) for p, o, _ in sample), default=0),
          evicted=sum(1 for *_, e in sample if e),
          program=check.summary(prog),
          control=check.summary(ctrl) if control else None)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        print("slobench: --seed must be >= 0 and --seconds > 0",
              file=sys.stderr)
        return 2
    try:
        cell = Cell.load(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (Refused, KeyError, FileNotFoundError) as e:
        print(f"slobench: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
