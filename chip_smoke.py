"""Chip smoke: serve full-width llama3.2-3b on one TPU through the paged engine.

    python chip_smoke.py              # one chip: kernels, engine, HTTP
    python chip_smoke.py --mesh 1x4   # four chips: sharded engine vs one chip

Weights are random (seed 0) and nothing is downloaded. One process holds the
chip(s) for the whole run. Without a TPU, or outside a checkout of this
repository, it exits non-zero and prints no result line.

With no arguments it runs, in order:

1. ``kernels`` -- both fused paged-attention Pallas kernels (full and
   partial-softmax) at llama3.2-3b widths against their jnp oracles.
2. ``engine`` -- ``EngineCore`` (paged KV, prefix cache on, 16384 KV tokens)
   behind ``InferenceServer`` serves 8 requests, two of which share a
   256-token prefix. Checks: every request finishes, one token readback per
   round, a prefix-cache hit, the Pallas kernels (``tpu_custom_call``) inside
   the lowered decode and chunk steps, and every emitted token within
   ``REGRET_TOL`` logits of the top logit of the plain (non-paged) forward.
3. ``http`` -- one request through ``HttpFrontend`` on a free port, served
   by the same engine from a thread of this process; its tokens pass the
   same dense-forward check (exact equality with the in-process tokens is
   reported, not required: a prefix-cache hit changes the batch shapes, and
   bf16 results may round differently per shape), and shutdown must drain
   with every page returned.

``--mesh 1x4`` runs only the same request set on the single-device engine and
on the sharded one, and compares their greedy tokens and readbacks per round.

Lines before the last are labels (device, compile seconds, shapes, wall time
per phase, tokens, peak device bytes), not measurements of record. The last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import gc
import json
import math
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "llama3.2-3b"
SEED = 0
KV_TOKENS = 16384
PAGE_SIZE = 16
MAX_BUDGET = 512
# unshared prompt lengths, plus two prompts of SHARED_PREFIX + tail tokens
PROMPT_LENS = (64, 128, 192, 512, 768, 1024)
SHARED_PREFIX = 256
SHARED_TAILS = (64, 128)
OUTPUT_LENS = (16, 24, 32, 16, 32, 24, 16, 32)
HTTP_OUTPUT = 16
BF16_TOL = dict(atol=2e-2, rtol=2e-2)   # kernel vs oracle (tests/test_kernels)
REGRET_TOL = 0.25     # logits: top dense logit minus the engine token's logit
REF_LEN = 1152        # dense-reference padding (>= longest prompt + output)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class CompileClock:
    """Sums XLA compile time (a persistent-cache hit counts its read time)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == COMPILE_EVENT:
                self.seconds += duration
                self.count += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return {"compile_s": self.seconds, "compiles": self.count,
                "cache_hits": self.cache_hits}


def require_tpu():
    """The device, or exit non-zero: no CPU fallback, no partial checkout."""
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no src/repro next to {os.path.basename(__file__)}; "
             f"run it from a checkout of the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    return dev


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


# ---------------------------------------------------------------------------
# 1. kernels vs oracles
# ---------------------------------------------------------------------------
def kernel_phase(cfg) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention.kernel import paged_attention_fused
    from repro.kernels.paged_attention.ops import dma_depth
    from repro.kernels.paged_attention.ref import paged_attention_fused_ref
    from repro.kernels.paged_prefill_attention.kernel import (
        paged_prefill_attention_fused)
    from repro.kernels.paged_prefill_attention.ref import (
        paged_prefill_attention_fused_ref)
    from repro.kernels.ref_common import finalize_partials

    Hkv, G, D = cfg.num_kv_heads, cfg.q_per_kv, cfg.resolved_head_dim
    ps, P = PAGE_SIZE, KV_TOKENS // PAGE_SIZE + 1
    n = 64                                     # 1024-token block tables
    scale = D ** -0.5
    depth = dma_depth()
    rng = np.random.default_rng(SEED)
    key = jax.random.PRNGKey(SEED)
    kq, kkv, kpq = jax.random.split(key, 3)
    kvp = jax.random.normal(kkv, (Hkv, P, 2, ps, D), jnp.bfloat16)
    out = {"dma_depth": depth}

    def compare(name, got, ref, valid=None):
        got = np.asarray(got, np.float32)
        ref = np.asarray(ref, np.float32)
        if valid is not None:
            got, ref = got[valid], ref[valid]
        if not np.all(np.isfinite(got)):
            fail(f"{name}: non-finite kernel output")
        err = float(np.max(np.abs(got - ref)))
        if not np.allclose(got, ref, **BF16_TOL):
            fail(f"{name}: kernel vs oracle max abs err {err}")
        out[f"{name}_max_abs_err"] = err

    # decode: 64 rows over ragged lengths, page-boundary and 1-token edges
    B = 64
    q = jax.random.normal(kq, (B, Hkv * G, D), jnp.bfloat16)
    bt = jnp.asarray(rng.integers(0, P - 1, (B, n)), jnp.int32)
    lens = rng.integers(1, n * ps + 1, B)
    lens[:3] = (n * ps, ps, 1)
    lens = jnp.asarray(lens, jnp.int32)
    dec = jax.jit(functools.partial(paged_attention_fused, scale=scale,
                                    dma_depth=depth))
    dec_p = jax.jit(functools.partial(paged_attention_fused, scale=scale,
                                      dma_depth=depth, partial=True))
    full = dec(q, kvp, bt, lens)
    compare("decode", full, paged_attention_fused_ref(q, kvp, bt, lens,
                                                      scale=scale))
    acc, _, l = dec_p(q, kvp, bt, lens)
    fin = finalize_partials(acc, l, q.dtype)
    compare("decode_partial", fin, full)
    out["decode_partial_bit_exact"] = bool(np.array_equal(
        np.asarray(fin), np.asarray(full)))

    # prefill: 4 ragged chunk rows of 512 queries (one ends mid-chunk)
    R, Sq = 4, 512
    qp = jax.random.normal(kpq, (R, Sq, Hkv, G, D), jnp.bfloat16)
    btp = jnp.asarray(rng.integers(0, P - 1, (R, n)), jnp.int32)
    pos = np.asarray([0, 256, 512, 512], np.int32)
    plen = pos + Sq
    plen[1] = pos[1] + 100
    pos, plen = jnp.asarray(pos), jnp.asarray(plen)
    valid = (np.asarray(pos)[:, None] + np.arange(Sq)[None, :]
             < np.asarray(plen)[:, None])
    pre = jax.jit(functools.partial(paged_prefill_attention_fused,
                                    scale=scale, dma_depth=depth))
    pre_p = jax.jit(functools.partial(paged_prefill_attention_fused,
                                      scale=scale, dma_depth=depth,
                                      partial=True))
    full = pre(qp, kvp, btp, pos, plen)
    compare("prefill", full, paged_prefill_attention_fused_ref(
        qp, kvp, btp, pos, plen, scale=scale), valid)
    acc, _, l = pre_p(qp, kvp, btp, pos, plen)
    fin = finalize_partials(acc, l, qp.dtype)
    compare("prefill_partial", fin, full, valid)
    out["prefill_partial_bit_exact"] = bool(np.array_equal(
        np.asarray(fin)[valid], np.asarray(full)[valid]))
    return out


# ---------------------------------------------------------------------------
# 2. engine behind InferenceServer
# ---------------------------------------------------------------------------
def make_workload(vocab: int):
    """[(prompt int32 array, max_output)]: PROMPT_LENS unshared prompts and
    two prompts that share a SHARED_PREFIX-token prefix."""
    import numpy as np
    rng = np.random.default_rng(SEED)
    shared = rng.integers(1, vocab, SHARED_PREFIX)
    prompts = [rng.integers(1, vocab, n) for n in PROMPT_LENS[:3]]
    prompts += [np.concatenate([shared, rng.integers(1, vocab, t)])
                for t in SHARED_TAILS]
    prompts += [rng.integers(1, vocab, n) for n in PROMPT_LENS[3:]]
    return [(p.astype(np.int32), m) for p, m in zip(prompts, OUTPUT_LENS)]


class StepRecorder:
    """Wraps an engine's jitted step and keeps the abstract arguments of its
    first call, so the program it dispatched can be lowered again and read."""

    def __init__(self, fn):
        self.fn = fn
        self.args = None

    def __call__(self, *args):
        if self.args is None:
            import jax
            self.args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding), args)
        return self.fn(*args)

    def lowered_text(self) -> str:
        if self.args is None:
            fail("step never dispatched")
        return self.fn.lower(*self.args).as_text()


def build_server(cfg, mesh=None):
    from repro.core import SlidingServeScheduler
    from repro.serving.engine import EngineCore
    from repro.serving.server import InferenceServer
    sched = SlidingServeScheduler(max_budget=MAX_BUDGET, max_iter_time=2.0)
    core = EngineCore(cfg, sched, cache_mode="paged",
                      kv_capacity_tokens=KV_TOKENS, page_size=PAGE_SIZE,
                      prefix_cache=True, mesh=mesh, seed=SEED)
    return InferenceServer(core)


def serve_workload(server, work) -> list:
    """Submit every request at once, run to completion, check the serving
    invariants; returns each request's token list."""
    handles = [server.submit(p, slo_class="standard", max_output=m)
               for p, m in work]
    server.run(max_wall_s=900.0)
    for h, (p, m) in zip(handles, work):
        if not (h.finished and h.finish_reason == "length"
                and len(h.collected) == m):
            fail(f"rid {h.rid}: finished={h.finished} "
                 f"reason={h.finish_reason!r} tokens={len(h.collected)}/{m}")
    st = server.core.stats
    if st.token_readbacks != st.iterations:
        fail(f"{st.token_readbacks} readbacks over {st.iterations} rounds")
    return [list(h.collected) for h in handles]


def dense_logits(cfg, params):
    """``f(prompt, gen) -> (top2 [m, 2], pick [m])``: the plain forward
    (blockwise jnp attention over a dense sequence, no pages), teacher-forced
    on ``prompt + gen[:-1]``; at generated position i, the two top logits and
    the logit of ``gen[i]``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import RunCtx, _head, forward
    rctx = RunCtx(block_q=128, block_k=128)
    width = max(OUTPUT_LENS)

    @jax.jit
    def dense(params, tokens, positions, chosen):
        x, _, _, _ = forward(cfg, params, tokens, rctx=rctx, mode="train")
        logits = _head(cfg, params, x[0, positions]).astype(jnp.float32)
        top2 = jax.lax.top_k(logits, 2)[0]
        pick = jnp.take_along_axis(logits, chosen[:, None], axis=1)[:, 0]
        return top2, pick

    def f(prompt, gen):
        m = len(gen)
        seq = np.zeros((1, REF_LEN), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + m - 1] = gen[:-1]
        positions = np.full((width,), len(prompt) - 1, np.int32)
        positions[:m] += np.arange(m, dtype=np.int32)
        chosen = np.zeros((width,), np.int32)
        chosen[:m] = gen
        top2, pick = jax.device_get(dense(params, seq, positions, chosen))
        return top2[:m], pick[:m]
    return f


def reference_check(cfg, params, work, outputs) -> dict:
    """Every engine token must be within REGRET_TOL of the top logit of the
    plain forward at its position (``dense_logits``)."""
    import numpy as np
    dense = dense_logits(cfg, params)
    regrets, margins = [], []
    for (prompt, _), gen in zip(work, outputs):
        top2, pick = dense(prompt, gen)
        regrets.extend((top2[:, 0] - pick).tolist())
        margins.extend((top2[:, 0] - top2[:, 1]).tolist())
    if not np.all(np.isfinite(regrets)):
        fail("non-finite dense reference logits")
    worst = float(max(regrets))
    if worst > REGRET_TOL:
        fail(f"engine token {worst} logits below the dense top logit")
    return {"tokens_checked": len(regrets),
            "argmax_agree": int(np.sum(np.asarray(regrets) <= 0.0)),
            "worst_regret": worst,
            "median_top2_margin": float(np.median(margins))}


def engine_phase(cfg, clock) -> tuple:
    t0 = time.perf_counter()
    server = build_server(cfg)
    core = server.core
    log("engine_build", wall_s=time.perf_counter() - t0, **clock.snapshot())

    core._jit_decode_fused = decode = StepRecorder(core._jit_decode_fused)
    core._jit_chunk_fused = chunk = StepRecorder(core._jit_chunk_fused)
    work = make_workload(cfg.vocab_size)
    c0, t0 = clock.seconds, time.perf_counter()
    outputs = serve_workload(server, work)
    st, ci = core.stats, core.cache_info()
    log("engine_serve", wall_s=time.perf_counter() - t0,
        compile_s=clock.seconds - c0, requests=len(work),
        prompt_tokens=st.prompt_tokens, prefill_tokens=st.prefill_tokens,
        decode_tokens=st.decode_tokens,
        output_tokens=sum(len(o) for o in outputs),
        iterations=st.iterations, token_readbacks=st.token_readbacks,
        prefill_calls=st.prefill_calls, decode_calls=st.decode_calls,
        compiled_shapes=st.compiled_shapes, cache_hit_tokens=ci["hit_tokens"],
        evictions=st.evictions)
    if ci["hit_tokens"] <= 0:
        fail("no prefix-cache hit on the shared-prefix pair")

    kernels = {name: "tpu_custom_call" in rec.lowered_text()
               for name, rec in (("decode", decode), ("chunk", chunk))}
    log("engine_lowered", tpu_custom_call=kernels)
    if not all(kernels.values()):
        fail(f"Pallas kernel missing from a lowered step: {kernels}")

    t0 = time.perf_counter()
    ref = reference_check(cfg, core.params, work, outputs)
    log("engine_reference", wall_s=time.perf_counter() - t0, **ref)
    return server, work, outputs


# ---------------------------------------------------------------------------
# 3. one request over HTTP/SSE
# ---------------------------------------------------------------------------
def http_phase(server, prompt, max_output) -> list:
    from repro.frontend.client import EngineHttpClient
    from repro.frontend.http_server import HttpFrontend

    fe = HttpFrontend(server, port=0, drain_s=60.0)
    done = {}

    def run():
        try:
            done["report"] = asyncio.run(fe.serve_forever())
        except BaseException as e:          # surfaced on the main thread
            done["error"] = repr(e)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    t_end = time.perf_counter() + 60.0
    while fe.port == 0 and time.perf_counter() < t_end and th.is_alive():
        time.sleep(0.02)
    cli = EngineHttpClient(port=fe.port, timeout=300.0)
    cli.wait_ready(60.0)
    t0 = time.perf_counter()
    h = cli.generate(prompt.tolist(), max_output=max_output)
    got = h.result()
    wall = time.perf_counter() - t0
    fe.request_stop()
    th.join(timeout=120.0)
    if th.is_alive() or "error" in done:
        fail(f"HTTP server did not drain: {done.get('error', 'timeout')}")
    if h.finish_reason != "length" or len(got) != max_output:
        fail(f"HTTP request: reason={h.finish_reason!r} "
             f"tokens={len(got)}/{max_output}")
    log("http", wall_s=wall, tokens=len(got), drain=done["report"])
    return got


# ---------------------------------------------------------------------------
# --mesh: sharded engine vs single device, same requests
# ---------------------------------------------------------------------------
def first_divergence(a, b):
    for rid, (x, y) in enumerate(zip(a, b)):
        for step, (u, v) in enumerate(zip(x, y)):
            if u != v:
                return rid, step
    return None


def mesh_phase(cfg, spec: str, clock) -> int:
    import jax

    from repro.launch.mesh import make_serving_mesh, parse_mesh_spec
    need = math.prod(parse_mesh_spec(spec)[0])
    if len(jax.devices()) < need:
        fail(f"--mesh {spec} needs {need} devices, JAX has "
             f"{len(jax.devices())}")
    work = make_workload(cfg.vocab_size)
    runs = {}
    for name, mesh in (("single", None), ("mesh", spec)):
        t0, c0 = time.perf_counter(), clock.seconds
        server = build_server(cfg, make_serving_mesh(mesh) if mesh else None)
        outputs = serve_workload(server, work)
        st = server.core.stats
        runs[name] = outputs
        log(f"mesh_{name}", mesh=mesh, shard=server.core.shard_info(),
            wall_s=time.perf_counter() - t0, compile_s=clock.seconds - c0,
            iterations=st.iterations, token_readbacks=st.token_readbacks,
            readbacks_per_round=st.token_readbacks / max(st.iterations, 1),
            compiled_shapes=st.compiled_shapes,
            output_tokens=sum(len(o) for o in outputs),
            peak_bytes=peak_bytes(jax.devices()[:need]))
        if name == "single":
            del server
            gc.collect()
    div = first_divergence(runs["single"], runs["mesh"])
    if div is not None:
        rid, step = div
        single = runs["single"][rid]
        # top-2 margin of the dense forward at the divergent position,
        # teacher-forced on the single-device tokens before it
        top2, _ = dense_logits(cfg, server.core.params)(
            work[rid][0], single[:step + 1])
        log("mesh_divergence", rid=rid, step=step,
            single=single[step], mesh=runs["mesh"][rid][step],
            top2_margin=float(top2[step, 0] - top2[step, 1]))
        fail(f"--mesh {spec}: rid {rid} diverges at step {step}")
    log("mesh_compare", requests=len(work),
        tokens=sum(len(o) for o in runs["mesh"]), identical=True)
    return need


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", default=None,
                    help="e.g. 1x4: run only the sharded-vs-single-device "
                         "comparison on that serving mesh")
    args = ap.parse_args(argv)
    dev = require_tpu()

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(jax.devices()), jax=jax.__version__, cache_dir=cache_dir)
    cfg = get_config(ARCH)
    t_start = time.perf_counter()

    if args.mesh:
        used = mesh_phase(cfg, args.mesh, clock)
    else:
        used = 1
        t0 = time.perf_counter()
        res = kernel_phase(cfg)
        log("kernels", wall_s=time.perf_counter() - t0, **res,
            **clock.snapshot())
        gc.collect()
        server, work, outputs = engine_phase(cfg, clock)
        prompt = work[0][0]
        got = http_phase(server, prompt, HTTP_OUTPUT)
        log("http_reference",
            matches_inprocess=got == outputs[0][:HTTP_OUTPUT],
            **reference_check(cfg, server.core.params,
                              [(prompt, HTTP_OUTPUT)], [got]))
    log("summary", total_wall_s=time.perf_counter() - t_start,
        peak_bytes=peak_bytes(jax.devices()[:used]), **clock.snapshot())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
