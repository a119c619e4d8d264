"""Serving launcher: the streaming InferenceServer on a real model.

On this container it serves reduced configs on CPU; on TPU the same entry
point builds the production mesh and shards the step functions (the engine
loop is identical — see repro/serving/engine.py). Requests are submitted
through the online API at their arrival times (open-loop) and tokens stream
back through per-request handles.

    python -m repro.launch.serve --arch llama3.2-3b --requests 8
    python -m repro.launch.serve --no-smoke --slo-class interactive ...
    REPRO_FORCE_MESH=2x4 python -m repro.launch.serve --cache-mode paged
    python -m repro.launch.serve --mesh 2x4 ...   # same thing, explicit

``--mesh``/``REPRO_FORCE_MESH`` (the shared helper in ``launch/mesh.py``)
runs the paged executor under jit + shard_map: KV page pools shard attention
heads on the ``model`` axis (or fall back to sequence-sharded attention),
while the scheduler stack and all host state stay mesh-oblivious.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_config
from repro.core import SlidingServeScheduler
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import add_mesh_argument, make_serving_mesh
from repro.serving.engine import EngineCore
from repro.serving.request import Request
from repro.serving.server import SLO_CLASSES, InferenceServer
from repro.serving.workloads import run_open_loop


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--qps", type=float, default=2.0)
    # --smoke/--no-smoke boolean pair (a bare store_true with default=True
    # made the full-size configs unreachable from the CLI)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduce the model config for CPU smoke runs "
                         "(--no-smoke serves the full-size architecture)")
    ap.add_argument("--max-budget", type=int, default=512)
    ap.add_argument("--slo-class", default="standard",
                    choices=sorted(SLO_CLASSES),
                    help="named tenant class (ttft/tbt SLO pair) submitted "
                         "requests run under")
    ap.add_argument("--cache-mode", default="auto",
                    choices=["auto", "slot", "paged"],
                    help="paged = block-table KV (production layout); "
                         "slot = contiguous rows (recurrent/MLA archs)")
    ap.add_argument("--kv-tokens", type=int, default=4096,
                    help="paged KV capacity in tokens")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reuse frozen KV pages across requests sharing a "
                         "token prefix (paged mode; greedy tokens are "
                         "bit-identical either way)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft up to K tokens per "
                         "decode-eligible request per round (paged mode; "
                         "n-gram prompt-lookup drafter; greedy tokens are "
                         "bit-identical to --spec-k 0)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling cutoff (0 = full vocabulary)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="PRNG seed for non-greedy sampling (runs are "
                         "deterministic per seed)")
    ap.add_argument("--serve-http", action="store_true",
                    help="expose the server over HTTP/SSE instead of "
                         "replaying a synthetic workload (SIGINT drains "
                         "gracefully; see repro.frontend.http_server)")
    ap.add_argument("--port", type=int, default=8763,
                    help="HTTP port for --serve-http (0 picks a free one)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="with --serve-http: >1 runs N engine replicas "
                         "behind the prefix-affine router")
    add_mesh_argument(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.serve_http:
        # the network front door owns engine construction (it builds N
        # replicas for the router); mesh serving stays on the in-process path
        import asyncio

        from repro.frontend.http_server import HttpFrontend, build_backend
        backend = build_backend(
            arch=args.arch, smoke=args.smoke, replicas=args.replicas,
            cache_mode=args.cache_mode, kv_tokens=args.kv_tokens,
            page_size=args.page_size, max_budget=args.max_budget,
            prefix_cache=args.prefix_cache, spec_k=args.spec_k,
            temperature=args.temperature, top_k=args.top_k,
            sample_seed=args.sample_seed)
        frontend = HttpFrontend(backend, port=args.port)
        asyncio.run(frontend.serve_forever())
        return None

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    mesh = make_serving_mesh(args.mesh)
    sched = SlidingServeScheduler(max_budget=args.max_budget, max_iter_time=2.0)
    core = EngineCore(cfg, sched, cache_mode=args.cache_mode,
                      max_slots=4, max_len=512,
                      kv_capacity_tokens=args.kv_tokens,
                      page_size=args.page_size, mesh=mesh,
                      prefix_cache=args.prefix_cache, spec_k=args.spec_k,
                      temperature=args.temperature, top_k=args.top_k,
                      sample_seed=args.sample_seed)
    server = InferenceServer(core)
    if core.mesh is not None:
        print(core.shard_banner())
    slo = SLO_CLASSES[args.slo_class]
    rng = np.random.default_rng(0)
    inter = rng.exponential(1.0 / args.qps, args.requests)
    arrivals = np.cumsum(inter)
    reqs = [Request(rid=i, arrival=float(arrivals[i]),
                    prompt_len=int(rng.integers(16, 128)),
                    max_output=int(rng.integers(4, 12)),
                    ttft_slo=slo.ttft_slo, tbt_slo=slo.tbt_slo,
                    slo_class=slo.name)
            for i in range(args.requests)]
    out = run_open_loop(server, reqs, max_wall_s=300.0)
    st = core.stats
    print(f"finished {len(out['finished'])}/{len(reqs)} "
          f"[{core.cache_mode} cache, slo={args.slo_class}]; "
          f"iterations={st.iterations} "
          f"max_concurrency={st.max_concurrency} evictions={st.evictions} "
          f"wall={out['wall']:.1f}s")
    if core.spec_k:
        si = core.spec_info()
        print(f"speculation: acceptance {si['acceptance_rate']:.0%} "
              f"({si['accepted_tokens']}/{si['draft_tokens']} drafts), "
              f"{si['tokens_per_verify_row']:.2f} tokens/verify row")
    if core.cache_mode == "paged" and core.prefix_cache:
        ci = core.cache_info()
        print(f"prefix cache: hit {ci['hit_tokens']}/{ci['prompt_tokens']} "
              f"prompt tokens ({ci['hit_rate']:.0%}), "
              f"{ci['cached_pages']} pages cached")
    for h in out["finished"]:
        r = h.request
        print(f"  req {r.rid}: ttft={(r.first_token_time - r.arrival):.2f}s "
              f"out={h.collected}")
    return out


if __name__ == "__main__":
    main()
