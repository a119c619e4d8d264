"""Find a configuration's knee under a traffic mix, on the chip.

    python3 -m slobench.sweep --config qwen3-1.7b --traffic <mix> \
        --rates 2,4,6,8 --seconds 40 --seed 11 [--calibrate]

One process builds the engine once (weights from ``--seed``), warms the
mix's listed shapes, and then:

* with ``--calibrate``, measures the exclusive prefill time of single
  prompts of several lengths on the otherwise idle engine (time from
  submission to first token, the second of two fresh prompts of each
  length), which the mix files keep as their ``exclusive_prefill_s``;
* for each rate, replays the mix open-loop (lead-in, window, lead-out)
  and prints the share of window requests that met both limits, the tails,
  the output rate, and the backlog (requests queued or admitted) at the
  window's two edges; what is left is cancelled before the next rate;
* prints every step shape the engine dispatched, for the mix's ``warm``
  list.

The knee is the highest rate at which the backlog does not grow and no
window request is left unserved; the share of requests that met both limits
is printed beside each rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from slobench import spec
from slobench.hooks import backlog, build_server, seen_shapes, warm
from slobench.run import Cell, configure_jax, label, require_chip

CALIBRATE_LENS = (16, 64, 256, 512, 1024, 2048, 4096, 8192)


def calibrate(server, vocab: int, rng) -> list:
    core = server.core
    table = []
    for n in CALIBRATE_LENS:
        got = []
        for _ in range(2):
            h = server.submit(rng.integers(1, vocab, n).astype(np.int32),
                              max_output=2)
            t0 = h.request.arrival
            while h.first_token_t is None:
                server.step()
            got.append(h.first_token_t - t0)
            server.run(max_wall_s=60.0)
        table.append([n, got[-1]])
        print(json.dumps({"calibrate": n, "ttft_s": got}), flush=True)
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--calibrate", action="store_true")
    ap.add_argument("--census", type=float, default=0.0,
                    help="first replay the mix once at this rate, unmeasured, "
                         "so that the sweep's rates find their shapes compiled")
    args = ap.parse_args(argv)

    from slobench import hooks, measure, openloop, traffic, weights
    bench = spec.load_benchmark()
    cell = Cell("sweep", 1, spec.load_config_by_name(args.config, bench),
                spec.load_traffic(args.traffic), [], [])
    devices = require_chip(1)
    configure_jax()
    d0 = devices[0]
    dev = {"platform": d0.platform, "kind": d0.device_kind, "count": 1}
    w, params = weights.make(cell.conf, args.seed)
    cfg = spec.family(cell.conf).model_config(cell.conf)
    server = build_server(cell.conf, cfg, params)
    core = server.core
    mem = d0.memory_stats() or {}
    label(dev, "built", peak_bytes=mem.get("peak_bytes_in_use"),
          bytes_limit=mem.get("bytes_limit"))
    t = time.perf_counter()
    warm(core, cell.mix["warm"])
    label(dev, "warm", seconds=time.perf_counter() - t)
    rng = np.random.default_rng(args.seed)
    mix = dict(cell.mix)
    if args.calibrate:
        mix["exclusive_prefill_s"] = calibrate(server, cell.conf["vocab_size"],
                                               rng)
        label(dev, "calibrated", exclusive_prefill_s=mix["exclusive_prefill_s"])
    rid0 = 10 ** 6
    rates = [float(r) for r in args.rates.split(",")]
    if args.census:
        rates = [-args.census] + rates
    for rate in rates:
        census, rate = rate < 0, abs(rate)
        seen0 = seen_shapes(core)
        mix["rate_rps"] = rate
        arrivals = traffic.make(mix, args.seconds, args.seed,
                                cell.conf["vocab_size"])
        probe = hooks.Probe(server)
        edges = {}

        def on_window(edge):
            edges[edge] = backlog(core)

        n_ev = len(server.events)
        res = openloop.run(server, probe, arrivals,
                         lambda a: traffic.limits(mix, a), args.seconds,
                         mix["lead_in_s"], mix["lead_out_s"], on_window,
                         rid0=rid0)
        rid0 += len(arrivals)
        win = res["window"]
        reqs = measure.from_events(server.events[n_ev:], res["meta"])
        w0, w1 = win.w0, win.w1
        inw = measure.in_window(reqs, w0, w1)
        ttft = measure.ttfts(reqs, w0, w1, res["end"])
        gaps = measure.tbt_gaps(reqs, w0, w1)
        met = sum(measure.met_limits(r, w1) for r in inw)
        q = lambda v, p: 1e3 * measure.nearest_rank(v, p) if v else None
        label(dev, "census" if census else "rate", rate_rps=rate,
              new_shapes=sorted(seen_shapes(core) - seen0), requests=len(inw),
              attainment=met / max(len(inw), 1), goodput_rps=met / args.seconds,
              ttft_p50_ms=q(ttft, .5), ttft_p95_ms=q(ttft, .95),
              tbt_p50_ms=q(gaps, .5), tbt_p99_ms=q(gaps, .99),
              output_tok_s=measure.output_rate(reqs, w0, w1),
              backlog_start=edges.get("start"), backlog_end=edges.get("end"),
              unserved=measure.unserved(reqs, w0, w1),
              evictions=win.stats1["evictions"] - win.stats0["evictions"],
              late_max_ms=1e3 * max(res["late"], default=0.0),
              peak_live_pages=max((n for _, n in probe.live_pages), default=0),
              pages=core.alloc.num_blocks)
        for rid, h in res["handles"].items():
            if not h.finished:
                server.cancel(rid)
        server.run(max_wall_s=60.0)
    label(dev, "shapes", seen=sorted(seen_shapes(core)))
    mem = (d0.memory_stats() or {})
    label(dev, "memory", peak_bytes=mem.get("peak_bytes_in_use"),
          bytes_limit=mem.get("bytes_limit"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
