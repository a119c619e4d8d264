"""Paged serving engine tests: slot-vs-paged output equivalence, allocator
eviction/recompute round-trip through the real engine, and the concurrency /
dispatch-count acceptance properties of the fused mixed-batch design."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import SlidingServeScheduler
from repro.serving.block_allocator import BlockAllocator
from repro.serving.engine import ServingEngine
from repro.serving.request import ReqState, Request


def _mk_requests(spec):
    return [Request(rid=i, arrival=a, prompt_len=p, max_output=o,
                    ttft_slo=900.0, tbt_slo=900.0)
            for i, (a, p, o) in enumerate(spec)]


def _serve(cfg, prompts, spec, **engine_kw):
    reqs = _mk_requests(spec)
    sched = SlidingServeScheduler(max_budget=256, max_iter_time=5.0)
    eng = ServingEngine(cfg, sched, seed=0, **engine_kw)
    out = eng.serve(reqs, {k: v.copy() for k, v in prompts.items()},
                    max_wall_s=900.0)
    return eng, out


# ---------------------------------------------------------------------------
# allocator page-id layer
# ---------------------------------------------------------------------------
def test_allocator_page_ids_and_victim_policy():
    a = BlockAllocator(capacity_tokens=256, block_size=16)   # 16 pages
    assert a.admit(1, 40) and a.admit(2, 40)                 # 3 pages each
    t1, t2 = a.page_table(1), a.page_table(2)
    assert len(t1) == 3 and len(t2) == 3 and not set(t1) & set(t2)
    assert a.grow(1, 70)                                     # 5 pages
    assert a.page_table(1)[:3] == t1                         # ids are stable
    a.check_invariants()
    # victim = lowest priority (largest key), never the needy request
    assert a.pick_victim(1, priority=lambda rid: rid) == 2
    assert a.pick_victim(2, priority=lambda rid: rid) == 1
    a.evict(2)
    assert a.evictions == 1 and 2 not in a.owners
    a.free(1)
    a.check_invariants()
    assert a.free_blocks == a.num_blocks


def test_allocator_free_tokens_counts_tail_slack():
    a = BlockAllocator(capacity_tokens=160, block_size=16)   # 10 pages
    assert a.admit(7, 20)    # 2 pages, 12 tokens of tail slack
    assert a.free_tokens() == 8 * 16 + 12


# ---------------------------------------------------------------------------
# engine equivalence + acceptance properties
# ---------------------------------------------------------------------------
def test_slot_vs_paged_same_greedy_tokens():
    cfg = get_config("llama3.2-3b").smoke()
    rng = np.random.default_rng(1)
    spec = [(0.0, 24, 4), (0.0, 51, 4), (0.0, 37, 3)]
    prompts = {i: rng.integers(1, cfg.vocab_size, p).astype(np.int32)
               for i, (_, p, _) in enumerate(spec)}
    _, out_slot = _serve(cfg, prompts, spec, cache_mode="slot",
                         max_slots=4, max_len=512)
    eng, out_paged = _serve(cfg, prompts, spec, cache_mode="paged",
                            kv_capacity_tokens=2048)
    assert not out_slot["unfinished"] and not out_paged["unfinished"]
    assert out_slot["outputs"] == out_paged["outputs"]
    assert eng.stats.evictions == 0


def test_paged_concurrency_beyond_slot_ceiling():
    """The paged engine admits strictly more concurrent requests than the
    slot engine's max_slots=8 ceiling, and a scheduler round costs at most
    two fused model dispatches no matter how many requests it names."""
    cfg = get_config("llama3.2-3b").smoke()
    rng = np.random.default_rng(2)
    spec = [(0.0, int(rng.integers(16, 48)), 2) for _ in range(12)]
    prompts = {i: rng.integers(1, cfg.vocab_size, p).astype(np.int32)
               for i, (_, p, _) in enumerate(spec)}
    eng, out = _serve(cfg, prompts, spec, cache_mode="paged",
                      max_slots=8, kv_capacity_tokens=4096)
    assert not out["unfinished"]
    assert eng.stats.max_concurrency > 8
    assert eng.stats.max_round_calls <= 2
    assert eng.alloc.free_blocks == eng.alloc.num_blocks  # all KV released


def test_eviction_recompute_roundtrip():
    """Saturate a tiny paged KV so decode growth must evict; the evicted
    request recomputes (prompt + already-emitted tokens) and every request
    still produces exactly the tokens an uncontended engine produces."""
    cfg = get_config("llama3.2-3b").smoke()
    rng = np.random.default_rng(3)
    spec = [(0.0, 60, 6) for _ in range(4)]
    prompts = {i: rng.integers(1, cfg.vocab_size, 60).astype(np.int32)
               for i in range(4)}
    # reference: ample capacity, no evictions
    ref_eng, ref = _serve(cfg, prompts, spec, cache_mode="paged",
                          kv_capacity_tokens=4096)
    assert ref_eng.stats.evictions == 0 and not ref["unfinished"]
    # contended: 4 x 60-token prompts round to exactly 16 pages; the 65th
    # token of each stream needs a 5th page -> growth failure -> eviction
    eng, out = _serve(cfg, prompts, spec, cache_mode="paged",
                      kv_capacity_tokens=256, page_size=16,
                      decode_reserve_tokens=0)
    assert not out["unfinished"], \
        f"unfinished after eviction: {[r.rid for r in out['unfinished']]}"
    assert eng.stats.evictions > 0, "KV was never contended"
    assert out["outputs"] == ref["outputs"], "recompute diverged from greedy"
    for r in out["finished"]:
        assert r.generated == 6 and len(out["outputs"][r.rid]) == 6
    eng.alloc.check_invariants()
    assert eng.alloc.free_blocks == eng.alloc.num_blocks


def test_oversized_allocation_splits_across_dispatches(monkeypatch):
    """An allocation above the top chunk bucket is split across dispatches
    (never silently truncated), and the split dispatches address only the
    page-table prefix they read — regression for the table-width overflow."""
    import repro.serving.engine as E
    monkeypatch.setattr(E, "CHUNK_BUCKETS", (16, 32))
    cfg = get_config("llama3.2-3b").smoke()
    rng = np.random.default_rng(4)
    spec = [(0.0, 100, 2)]
    prompts = {0: rng.integers(1, cfg.vocab_size, 100).astype(np.int32)}
    eng, out = _serve(cfg, prompts, spec, cache_mode="paged",
                      kv_capacity_tokens=1024)
    assert not out["unfinished"]
    assert eng.stats.prefill_calls >= 4      # 100 tokens over a 32-token cap
    _, ref = _serve(cfg, prompts, spec, cache_mode="slot",
                    max_slots=2, max_len=512)
    assert out["outputs"] == ref["outputs"]


def test_single_token_readback_per_round():
    """Zero-sync hot path: paged mode performs exactly one token-id
    device->host readback per executed scheduler round, regardless of how
    many requests a round batches — and the deferred-readback pipeline emits
    the same greedy tokens as the sync-every-row legacy mode."""
    cfg = get_config("llama3.2-3b").smoke()
    rng = np.random.default_rng(5)
    spec = [(0.0, int(rng.integers(16, 48)), 3) for _ in range(8)]
    prompts = {i: rng.integers(1, cfg.vocab_size, p).astype(np.int32)
               for i, (_, p, _) in enumerate(spec)}

    calls = []
    orig = ServingEngine._readback

    def spy(self, arr):
        calls.append(np.shape(arr))
        return orig(self, arr)

    ServingEngine._readback = spy
    try:
        eng, out = _serve(cfg, prompts, spec, cache_mode="paged",
                          kv_capacity_tokens=4096)
    finally:
        ServingEngine._readback = orig
    assert not out["unfinished"]
    st = eng.stats
    # _readback is the paged path's only sync point; one call per round.
    assert len(calls) == st.token_readbacks == st.iterations, (
        len(calls), st.token_readbacks, st.iterations)
    assert st.max_concurrency > 1      # rounds really were batched
    assert st.sync_s > 0.0
    # the readback and dispatch timers are the engine spans' durations
    tot = eng.spans.totals()
    assert tot["engine.readback"]["count"] == st.token_readbacks
    assert abs(tot["engine.readback"]["total_ms"] - 1e3 * st.sync_s) < 1e-6
    dispatches = [v for k, v in tot.items() if k.startswith("engine.dispatch.")]
    assert sum(v["count"] for v in dispatches) == (st.decode_calls
                                                   + st.prefill_calls)
    assert abs(sum(v["total_ms"] for v in dispatches)
               - 1e3 * st.dispatch_s) < 1e-6

    # legacy sync-every-row mode: same tokens, strictly more transfers
    eng2, out2 = _serve(cfg, prompts, spec, cache_mode="paged",
                        kv_capacity_tokens=4096, overlap=False)
    assert not out2["unfinished"]
    assert out2["outputs"] == out["outputs"]
    assert eng2.stats.token_readbacks > eng2.stats.iterations


def test_row_bucket_ladder_bounds_compiled_shapes(monkeypatch):
    """Concurrency above the top row bucket splits across dispatches instead
    of minting new compiled row shapes: every JIT'd shape uses a row count
    from ROW_BUCKETS, so compiled_shapes stays bounded no matter how many
    requests arrive."""
    import repro.serving.engine as E
    monkeypatch.setattr(E, "ROW_BUCKETS", (1, 2, 4))
    cfg = get_config("llama3.2-3b").smoke()
    rng = np.random.default_rng(6)
    spec = [(0.0, int(rng.integers(8, 24)), 3) for _ in range(10)]
    prompts = {i: rng.integers(1, cfg.vocab_size, p).astype(np.int32)
               for i, (_, p, _) in enumerate(spec)}
    eng, out = _serve(cfg, prompts, spec, cache_mode="paged",
                      kv_capacity_tokens=8192)
    assert not out["unfinished"]
    assert eng.stats.max_concurrency > 4   # really ran above the top rung
    rows_seen = {k[1] for k in eng._seen_shapes}
    assert rows_seen <= {1, 2, 4}, rows_seen
    # the ladder bounds the total shape universe:
    #   chunk shapes <= |rows| * |chunk buckets| * |table widths|, decode
    #   shapes <= |rows| * |table widths| — assert the cheap invariant that
    #   nothing outside the ladder was compiled.
    assert eng.stats.compiled_shapes == len(eng._seen_shapes)
    # outputs unaffected by the split
    _, ref = _serve(cfg, prompts, spec, cache_mode="slot",
                    max_slots=10, max_len=256)
    assert out["outputs"] == ref["outputs"]


def test_paged_rejects_recurrent_arch():
    cfg = get_config("xlstm-125m").smoke()
    sched = SlidingServeScheduler(max_budget=128)
    with pytest.raises(ValueError):
        ServingEngine(cfg, sched, cache_mode="paged")
    eng = ServingEngine(cfg, sched, cache_mode="auto", max_slots=2,
                        max_len=128)
    assert eng.cache_mode == "slot"
