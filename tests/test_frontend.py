"""Network front door tests: HTTP/SSE transport, prefix-affine router,
shared prefix directory, graceful drain, and in-flight burst sharing.

Pins the new-subsystem acceptance properties:

* SSE token streams over HTTP are **bit-identical** to the in-process API
  (greedy tokens depend only on the prompt — transport must not matter);
* cancelling over HTTP mid-stream aborts server-side and every KV page
  returns to the allocator;
* the router steers a shared-prefix stream onto the replica already holding
  the prefix (directory affinity) and spills to the least-loaded replica
  when the holder saturates;
* every replica keeps the one-readback-per-round zero-sync invariant under
  router pumping;
* a burst of requests sharing an uncommitted prefix defers the followers
  until the leader commits — the followers then prefill only their suffix
  (in-flight burst sharing), with greedy tokens unchanged;
* ``InferenceServer.close()`` drains, settles every handle, verifiably
  reclaims pages/slots, and refuses new admissions.
"""
import asyncio
import threading
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SlidingServeScheduler
from repro.frontend.client import EngineHttpClient
from repro.frontend.http_server import HttpFrontend, build_backend
from repro.frontend.prefix_directory import PrefixDirectory
from repro.frontend.router import EngineRouter, LocalReplica
from repro.serving.block_allocator import ROOT_CHAIN, page_chain_hash
from repro.serving.engine import EngineCore
from repro.serving.server import InferenceServer


def _server(cfg, **kw):
    kw.setdefault("max_budget", 256)
    budget = kw.pop("max_budget")
    kw.setdefault("kv_capacity_tokens", 2048)
    kw.setdefault("cache_mode", "paged")
    return InferenceServer.build(
        cfg, scheduler=SlidingServeScheduler(max_budget=budget,
                                             max_iter_time=5.0), **kw)


@pytest.fixture(scope="module")
def cfg():
    return get_config("llama3.2-3b").smoke()


# ---------------------------------------------------------------------------
# PrefixDirectory: pure unit semantics (no engine)
# ---------------------------------------------------------------------------
class TestPrefixDirectory:
    def test_chain_hashes_match_allocator_fold(self):
        d = PrefixDirectory(page_size=4)
        toks = list(range(10))
        chain = d.chain_hashes(toks)
        assert len(chain) == 2                      # whole pages only
        h0 = page_chain_hash(ROOT_CHAIN, toks[:4])
        assert chain == [h0, page_chain_hash(h0, toks[4:8])]

    def test_match_requires_contiguous_chain(self):
        d = PrefixDirectory(page_size=4)
        toks = list(range(12))
        chain = d.chain_hashes(toks)
        d.on_commit(0, chain[0])
        d.on_commit(0, chain[1])
        d.on_commit(1, chain[1])    # page 2 without page 1: unreachable
        m = d.match(toks)
        assert m == {0: 8}          # replica 1 holds no usable prefix

    def test_reclaim_drops_holder(self):
        d = PrefixDirectory(page_size=4)
        toks = list(range(8))
        chain = d.chain_hashes(toks)
        for h in chain:
            d.on_commit(0, h)
        assert d.match(toks) == {0: 8}
        d.on_reclaim(0, chain[1])
        assert d.match(toks) == {0: 4}
        d.on_reclaim(0, chain[0])
        assert d.match(toks) == {}
        assert d.pages_held(0) == 0

    def test_listener_adapter_and_stats(self):
        d = PrefixDirectory(page_size=4)
        lst = d.listener_for(2)
        h = page_chain_hash(ROOT_CHAIN, [1, 2, 3, 4])
        lst.on_commit(h, 1)
        assert d.match([1, 2, 3, 4, 9]) == {2: 4}
        st = d.stats()
        assert st["commits"] == 1 and st["hit_lookups"] == 1
        lst.on_reclaim(h)
        assert d.stats()["entries"] == 0


# ---------------------------------------------------------------------------
# in-flight burst sharing (defer-shared admission)
# ---------------------------------------------------------------------------
def _burst(cfg, n=4, system_len=64, unique_len=8, seed=3):
    rng = np.random.default_rng(seed)
    system = rng.integers(1, cfg.vocab_size, system_len).astype(np.int32)
    prompts = {i: np.concatenate(
        [system, rng.integers(1, cfg.vocab_size, unique_len).astype(np.int32)])
        for i in range(n)}
    return prompts


def test_burst_sharing_defers_followers_and_saves_prefill(cfg):
    """K requests sharing an uncommitted prefix arrive in one burst: the
    followers must wait for the leader's commits instead of prefilling the
    shared pages cold — asserted as computed-prefill savings vs the
    defer-disabled engine, with identical greedy tokens."""
    prompts = _burst(cfg)
    outs, computed, deferred = {}, {}, {}
    for defer in (True, False):
        srv = _server(cfg, defer_shared=defer)
        handles = {i: srv.submit(p.copy(), max_output=3)
                   for i, p in prompts.items()}
        srv.run(max_wall_s=900.0)
        assert all(h.finished for h in handles.values())
        outs[defer] = {i: list(h.collected) for i, h in handles.items()}
        computed[defer] = srv.core.stats.prefill_tokens
        deferred[defer] = srv.core.stats.deferred_admissions
    assert outs[True] == outs[False], "defer-shared changed greedy tokens"
    assert deferred[True] > 0, "burst never deferred a follower"
    assert deferred[False] == 0
    # 3 followers x 64 shared tokens = 192 potentially shared; deferral must
    # recover at least the whole pages of the shared prefix for them
    saved = computed[False] - computed[True]
    page = 16
    assert saved >= 3 * (64 // page * page - page), \
        f"only {saved} prefill tokens saved by deferral"


def test_defer_cannot_wedge_without_leader(cfg):
    """A lone request (no leader to wait for) must admit immediately even
    with deferral on; the cap bounds pathological waits."""
    srv = _server(cfg, defer_shared=True)
    h = srv.submit(np.arange(1, 40, dtype=np.int32), max_output=3)
    assert h.result(max_wall_s=900.0)
    assert srv.core.stats.deferred_admissions == 0


# ---------------------------------------------------------------------------
# graceful shutdown / drain
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["paged", "slot"])
def test_close_drains_and_reclaims(cfg, mode):
    srv = _server(cfg, cache_mode=mode) if mode == "paged" else \
        InferenceServer.build(
            cfg, scheduler=SlidingServeScheduler(max_budget=256,
                                                 max_iter_time=5.0),
            cache_mode="slot", max_slots=4, max_len=512)
    rng = np.random.default_rng(0)
    hs = [srv.submit(rng.integers(1, cfg.vocab_size, 24).astype(np.int32),
                     max_output=3) for _ in range(3)]
    report = srv.close(drain_s=120.0)
    assert report["drained"] and report["finished"] == 3
    assert all(h.finished for h in hs)
    # close() itself asserts pages/slots reclaimed; re-check from outside
    if mode == "paged":
        assert srv.core.alloc.free_blocks == srv.core.alloc.num_blocks
    else:
        assert len(srv.core.free_slots) == srv.core.max_slots
    with pytest.raises(RuntimeError):
        srv.submit(np.arange(1, 10, dtype=np.int32))
    assert srv.close() is report            # idempotent


def test_close_aborts_stragglers_at_deadline(cfg):
    srv = _server(cfg)
    h = srv.submit(np.arange(1, 60, dtype=np.int32), max_output=512)
    report = srv.close(drain_s=0.0)         # no time to drain: abort sweep
    assert h.finished and h.aborted
    assert report["aborted"] == 1
    assert srv.core.alloc.free_blocks == srv.core.alloc.num_blocks


# ---------------------------------------------------------------------------
# router: affinity, spillover, zero-sync per replica
# ---------------------------------------------------------------------------
def test_router_affinity_lands_shared_stream_on_one_replica(cfg):
    router = EngineRouter([LocalReplica(i, _server(cfg)) for i in range(2)])
    prompts = _burst(cfg, n=4, seed=5)
    # sequential: each request finishes (and commits) before the next routes
    owners = []
    for i, p in enumerate(prompts.values()):
        h = router.submit(p.copy(), max_output=3)
        router.run(max_wall_s=900.0)
        assert h.finished
        owners.append(router.owner_of(h.rid))
    # after the first commits, every follower must land on its holder
    assert len(set(owners[1:])) == 1 and owners[1] == owners[0]
    assert router.affine_hits >= len(prompts) - 1
    assert router.directory.stats()["hit_rate"] > 0.5
    # zero-sync invariant per replica under router pumping
    for rep in router.replicas:
        st = rep.server.core.stats
        assert st.token_readbacks == st.iterations
    report = router.close()
    assert report["drained"]


def test_router_spills_when_holder_saturated(cfg):
    router = EngineRouter([LocalReplica(i, _server(cfg)) for i in range(2)],
                          spill_factor=2.0)
    prompts = _burst(cfg, n=2, seed=6)
    first = router.submit(prompts[0].copy(), max_output=3)
    router.run(max_wall_s=900.0)
    holder = router.owner_of(first.rid)
    # saturate the holder: a large queued backlog it has not started
    rng = np.random.default_rng(9)
    for _ in range(6):
        router.replicas[holder].server.submit(
            rng.integers(1, cfg.vocab_size, 120).astype(np.int32),
            max_output=64)
    # the shared-prefix follower matches the holder but must spill away
    h = router.submit(prompts[1].copy(), max_output=3)
    assert router.owner_of(h.rid) != holder
    assert router.spills == 1
    router.run(max_wall_s=900.0)
    router.close()


def test_router_round_robin_ignores_directory(cfg):
    router = EngineRouter([LocalReplica(i, _server(cfg)) for i in range(2)],
                          policy="round-robin")
    prompts = _burst(cfg, n=4, seed=7)
    for p in prompts.values():
        router.submit(p.copy(), max_output=2)
        router.run(max_wall_s=900.0)
    assert router.routed == [2, 2]
    assert router.directory.stats()["lookups"] == 0
    router.close()


def test_router_parity_with_single_engine(cfg):
    prompts = _burst(cfg, n=3, seed=8)
    single = _server(cfg)
    ref = {i: single.submit(p.copy(), max_output=4).result(900.0)
           for i, p in prompts.items()}
    router = EngineRouter([LocalReplica(i, _server(cfg)) for i in range(2)])
    got = {}
    for i, p in prompts.items():
        h = router.submit(p.copy(), max_output=4)
        router.run(max_wall_s=900.0)
        got[i] = list(h.collected)
    assert got == ref, "routing changed greedy tokens"
    router.close()


# ---------------------------------------------------------------------------
# HTTP/SSE transport (in-thread server; the subprocess path is
# examples/router_smoke.py)
# ---------------------------------------------------------------------------
def _start_http(backend):
    fe = HttpFrontend(backend, port=0, drain_s=30.0)
    th = threading.Thread(target=lambda: asyncio.run(fe.serve_forever()),
                          daemon=True)
    th.start()
    cli = EngineHttpClient(port=0, timeout=300.0)
    t_end = time.perf_counter() + 60.0
    while fe.port == 0 and time.perf_counter() < t_end:
        time.sleep(0.02)
    cli.port = fe.port
    cli.wait_ready(60.0)
    return fe, cli, th


def _stop_http(fe, th):
    fe.request_stop()
    th.join(timeout=60.0)
    assert not th.is_alive(), "HTTP server failed to drain on stop"


@pytest.fixture()
def http_fe(cfg):
    backend = build_backend(replicas=1, kv_tokens=2048, max_budget=256)
    fe, cli, th = _start_http(backend)
    yield fe, cli, backend
    _stop_http(fe, th)


def test_http_front_door_after_inprocess_requests(cfg):
    """A front door attached to a server that already served requests takes
    rids from the server, so its requests never reuse a finished rid."""
    backend = build_backend(replicas=1, kv_tokens=2048, max_budget=256)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, 24)
    ref = backend.submit(prompt.astype(np.int32), max_output=4).result(900.0)
    fe, cli, th = _start_http(backend)
    try:
        h = cli.generate(prompt.tolist(), max_output=4)
        assert h.result() == ref
        assert h.rid == 1
    finally:
        _stop_http(fe, th)


def test_http_sse_parity_with_inprocess(cfg, http_fe):
    fe, cli, backend = http_fe
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in (24, 40, 33)]
    ref_srv = _server(cfg)
    ref = [ref_srv.submit(np.asarray(p, np.int32), max_output=4).result(900.0)
           for p in prompts]
    got = [cli.generate(p, max_output=4).result() for p in prompts]
    assert got == ref, "SSE stream diverged from the in-process API"


def test_http_cancel_mid_stream_reclaims_pages(cfg, http_fe):
    fe, cli, backend = http_fe
    rng = np.random.default_rng(4)
    h = cli.generate(rng.integers(1, cfg.vocab_size, 48).tolist(),
                     max_output=512)
    seen = 0
    for _ in h.tokens():
        seen += 1
        if seen == 1:
            assert h.cancel()
    assert h.aborted and seen < 512
    # the abort must have freed every page the request held; wait for the
    # pump to settle the engine then check the pool refilled
    core = backend.core
    t_end = time.perf_counter() + 60.0
    while core.has_work() and time.perf_counter() < t_end:
        time.sleep(0.02)
    held = core.alloc.num_blocks - core.alloc.free_blocks
    assert held == 0, f"{held} pages still live after HTTP cancel"
    assert core.stats.aborted == 1


@pytest.fixture()
def http_fe_spec(cfg):
    """Front door over a speculating engine whose drafter replays the known
    greedy stream — every draft is accepted, so each decode round emits a
    full multi-token burst (deterministic coverage for batched SSE frames)."""
    from repro.serving.drafter import DrafterBase

    rng = np.random.default_rng(12)
    prompt = rng.integers(1, cfg.vocab_size, 24).astype(np.int32)
    ref_srv = _server(cfg)
    ref = ref_srv.submit(prompt.copy(), max_output=8).result(900.0)

    class ReplayDrafter(DrafterBase):
        def propose(self, context, k):
            gen = len(context) - len(prompt)
            if gen < 0 or gen >= len(ref):
                return None
            out = np.asarray(ref[gen:gen + k], np.int32)
            return out if len(out) else None

    backend = build_backend(replicas=1, kv_tokens=2048, max_budget=256,
                            spec_k=4, drafter=ReplayDrafter())
    fe = HttpFrontend(backend, port=0, drain_s=30.0)
    th = threading.Thread(target=lambda: asyncio.run(fe.serve_forever()),
                          daemon=True)
    th.start()
    cli = EngineHttpClient(port=0, timeout=300.0)
    t_end = time.perf_counter() + 60.0
    while fe.port == 0 and time.perf_counter() < t_end:
        time.sleep(0.02)
    cli.port = fe.port
    cli.wait_ready(60.0)
    yield cli, backend, prompt, ref
    fe.request_stop()
    th.join(timeout=60.0)
    assert not th.is_alive(), "HTTP server failed to drain on stop"


def test_http_sse_batches_speculative_bursts(cfg, http_fe_spec):
    """A speculative round's burst arrives as ONE SSE `token` frame carrying
    `tokens: [ids]`, the stream equals the unspeculated reference, and the
    legacy single-`token` field still carries the frame's first id."""
    cli, backend, prompt, ref = http_fe_spec
    h = cli.generate(prompt.tolist(), max_output=8)
    got = h.result()
    assert got == ref, "speculative SSE stream diverged from greedy"
    frames = [d for name, d in h.events if name == "token"]
    assert frames, "no token frames seen"
    assert all("tokens" in d and d["token"] == d["tokens"][0] for d in frames)
    assert any(len(d["tokens"]) > 1 for d in frames), \
        "full-acceptance speculation never batched an SSE frame"
    # terminal frame counts every token of every burst
    fin = next(d for name, d in h.events if name == "finished")
    assert fin["n_tokens"] == len(ref)
    st = cli.stats()["engine"]
    assert st["spec_accepted"] > 0
    assert st["token_readbacks"] == st["iterations"]


def test_http_stats_and_draining_rejection(cfg, http_fe):
    fe, cli, backend = http_fe
    rng = np.random.default_rng(5)
    cli.generate(rng.integers(1, cfg.vocab_size, 24).tolist(),
                 max_output=2).result()
    st = cli.stats()
    assert st["engine"]["iterations"] > 0
    assert st["engine"]["token_readbacks"] == st["engine"]["iterations"]
    assert "cache_info" in st and "per_class" in st
    assert cli.load()["outstanding_tokens"] == 0
    assert cli.prefix_feed()["next"] > 0    # commits were exported
