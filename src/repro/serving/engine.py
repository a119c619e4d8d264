"""Real-execution serving engine: SlidingServe driving actual JAX forwards.

This is the end-to-end integration of the paper's scheduler with the model
substrate. Two cache designs share one serve loop:

* **paged** (default where the arch allows) — the production layout. KV lives
  in physical pages handed out by :class:`BlockAllocator`, which is the
  single admission/preemption authority (admit on free blocks, grow per
  emitted token, evict-and-recompute the lowest-priority owner when decode
  growth fails). A scheduler ``Decision`` within the row ladder executes as
  at most **two** fused JIT dispatches no matter how many requests it names
  (row counts above ``ROW_BUCKETS[-1]`` and chunks above the top chunk
  bucket split across extra dispatches): one ragged
  chunked-prefill batch (every prefill row at its own offset, vLLM-style
  slot-mapped page writes, the ``paged_prefill_attention`` kernel on TPU)
  and one ragged decode batch (``paged_attention`` kernel on TPU; both fall
  back to their jnp oracles on CPU). Concurrency is bounded by KV pages, not
  by a slot count, and KV pressure (`utilization`, evictions) is surfaced to
  ``SchedulerBase.schedule/observe`` so chunk budgets back off before
  allocation failures.

  The paged hot path is **zero-sync**: both fused steps sample greedily *on
  device* and return int32 token ids, the serve loop runs one round ahead of
  the device (JAX async dispatch), and the only device→host transfer is a
  single deferred token-id readback per scheduler round — round N's ids are
  pulled while round N+1's admission, scheduling and numpy batch assembly
  have already happened on the host. Block-table uploads are content-cached
  and reused across rounds. ``overlap=False`` restores the legacy
  sync-every-row behaviour for A/B profiling.
* **slot** (fallback for recurrent/MLA/enc-dec archs whose per-request state
  is not paged) — contiguous ``max_slots x max_len`` rows, per-request
  chunked prefill and lockstep ragged decode, as in the original engine.

  The paged executor is **mesh-aware**: pass ``mesh=`` (see
  ``launch/mesh.make_serving_mesh``) and the fused steps run under
  ``jax.jit`` + ``shard_map``. KV page pools shard attention heads on the
  ``model`` axis when the head count divides it, else stay replicated with
  sequence-sharded attention (``launch/sharding.paged_cache_specs`` mirrors
  the training cache rule); params and every host-derived operand — block
  tables, write slots, token ids — replicate, so the scheduler stack needs
  zero changes and greedy tokens are bit-identical to the single-device
  engine. The one-readback-per-round and ROW_BUCKETS invariants survive
  unchanged (token ids come back as one replicated [R] vector).

Wall-clock latencies feed the online predictor in both modes (paged observes
one round late, at the readback that proves the round finished). On CPU the
engine serves the reduced-config models (the examples use it); on TPU the
same loop drives the sharded step functions with the Pallas kernels
underneath.

The engine is **step-based** (vLLM/sglang-style online core): requests enter
continuously via ``add_request(req, prompt)``, leave via ``abort(rid)``, and
``step()`` runs exactly one scheduler round — admission, scheduling, the
fused dispatches, and the deferred one-readback-per-round flush — returning
the round's :class:`EngineEvent` list (QUEUED / ADMITTED / FIRST_TOKEN /
TOKEN / FINISHED / EVICTED / ABORTED, each with a timestamp and, for
token-bearing events, the token id). Token-bearing events of a paged round
surface one ``step()`` late, at the flush that reads the round's ids back.
``serve()`` is a thin offline compatibility wrapper that feeds a request
list through the same ``step()`` loop; ``repro.serving.server`` hosts the
streaming submit/cancel frontend.

Each paged round's host phases are spans of ``EngineCore.spans``
(``repro.serving.spans``): ``engine.round`` around ``step()``, and inside it
``engine.admit``, ``engine.schedule``, ``engine.grow``, ``engine.assemble``,
``engine.flush`` (child ``engine.readback``) and ``engine.dispatch.<kind>``
(children ``engine.upload`` and ``engine.compile`` or ``engine.launch``).
They are kept in memory, summed per name for operators (``GET /v1/stats``),
and written, with their args, into a profile when one is recording.
"""
from __future__ import annotations

import collections
import dataclasses
import enum
import heapq
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import MAMBA, MLSTM, SLSTM, ModelConfig
from repro.core.scheduler import KVPressure, SchedulerBase
from repro.models.model import (PAGED_KV_LAYOUT, RunCtx, Sampling,
                                chunk_prefill_step, decode_step, init_cache,
                                init_paged_cache, init_params,
                                paged_chunk_step, paged_decode_step,
                                paged_spec_step, supports_paged_cache)
from repro.serving.block_allocator import BlockAllocator
from repro.serving.drafter import DrafterBase, NGramDrafter
from repro.serving.request import ReqState, Request
from repro.serving.spans import Spans

# chunk-length ladder for JIT shape bucketing; allocations above the top rung
# are split across dispatches instead of being silently truncated.
CHUNK_BUCKETS = (16, 32, 64, 128, 256, 512, 1024, 2048)

# fused-batch *row* ladder: like CHUNK_BUCKETS but for the batch dimension.
# Row counts above the top rung are split across dispatches, so the set of
# compiled row shapes is bounded by this tuple no matter how high concurrency
# climbs (an unbounded next-pow2 ladder mints a fresh XLA program for every
# new power of two it meets).
ROW_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


def _bucket(n: int, buckets=CHUNK_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _row_bucket(n: int) -> int:
    return _bucket(n, ROW_BUCKETS)


def _pow2(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class EventKind(enum.Enum):
    QUEUED = "queued"            # request handed to the engine (arrival)
    ADMITTED = "admitted"        # KV/slot reserved; request is executable
    FIRST_TOKEN = "first_token"  # first output token (token id attached)
    TOKEN = "token"              # subsequent output token (token id attached)
    FINISHED = "finished"        # reason: "length" (max_output) | "stop" (EOS)
    EVICTED = "evicted"          # relegated by KV pressure; will re-prefill
    ABORTED = "aborted"          # cancelled via EngineCore.abort()


@dataclasses.dataclass
class EngineEvent:
    """One request-lifecycle transition, as observed by ``step()``.

    ``t`` is seconds on the engine clock (``EngineCore.now()``). For
    FIRST_TOKEN/TOKEN it is the *readback* time — when the id became
    host-visible — which for overlapped paged rounds is one round after
    dispatch."""

    kind: EventKind
    rid: int
    t: float
    token: Optional[int] = None
    reason: str = ""


@dataclasses.dataclass
class EngineStats:
    iterations: int = 0
    prefill_calls: int = 0        # fused chunk dispatches (paged) / per-req (slot)
    decode_calls: int = 0
    compiled_shapes: int = 0
    evictions: int = 0
    aborted: int = 0              # requests cancelled via abort()
    max_concurrency: int = 0      # peak simultaneously-admitted requests
    max_round_calls: int = 0      # peak model dispatches in one scheduler round
    # ---- prefix-cache accounting (paged mode) --------------------------------
    cache_hit_tokens: int = 0     # prompt tokens served from frozen pages
    prompt_tokens: int = 0        # prompt tokens admitted (hit-rate denominator)
    prefill_tokens: int = 0       # prompt tokens actually computed
    decode_tokens: int = 0        # output tokens computed (decode-step rows)
    deferred_admissions: int = 0  # admission rounds a follower waited for an
                                  # in-flight leader to commit a shared prefix
    # ---- zero-sync hot-path accounting (paged mode) --------------------------
    token_readbacks: int = 0      # device->host token-id transfers
    sync_s: float = 0.0           # wall in ``engine.readback`` spans
    dispatch_s: float = 0.0       # wall in ``engine.dispatch.*`` spans
    device_busy_s: float = 0.0    # wall time some round is in flight: from
                                  # its dispatch to the end of its flush, on
                                  # the host's clock (not the device's busy
                                  # time, which only a profile shows)
    reused_uploads: int = 0       # block-table uploads served from device cache
    # ---- speculative decoding (paged mode, spec_k > 0) -----------------------
    spec_calls: int = 0           # fused verify dispatches
    spec_rounds: int = 0          # rounds that dispatched >=1 verify row
    spec_rows: int = 0            # verify rows read back
    spec_drafts: int = 0          # draft tokens proposed (verify width - 1)
    spec_accepted: int = 0        # draft tokens the model accepted
    spec_emitted: int = 0         # tokens emitted by verify rows (accepted
                                  # drafts + bonus, after stop/length cuts)
    # ---- per-SLO-class breakdown (admission/eviction weight the class) ------
    finished_by_class: Dict[str, int] = dataclasses.field(default_factory=dict)
    evicted_by_class: Dict[str, int] = dataclasses.field(default_factory=dict)
    aborted_by_class: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class _InflightRound:
    """One dispatched-but-not-read-back scheduler round (paged mode)."""

    toks: List                    # device int32 vectors, one per dispatch
    emits: List[Tuple[int, int]]  # (rid, row in the concatenated tok vector)
    t_dispatch: float             # perf_counter at dispatch
    executed_batch: List = dataclasses.field(default_factory=list)
    # (req, token index, was_first, was_finish): timestamps provisionally
    # stamped at dispatch, corrected to readback time at flush.
    stamped: List = dataclasses.field(default_factory=list)
    # speculative verify rows: (rid, base offset of the row's [accepted,
    # out_0..out_{Lb-1}] span in the concatenated vector, Lb, n_real, start).
    spec_emits: List[Tuple[int, int, int, int, int]] = \
        dataclasses.field(default_factory=list)


class EngineCore:
    """Continuous-batching engine core executing a real model, driven one
    scheduler round at a time.

    Lifecycle: ``add_request(req, prompt)`` → ``step()`` (repeat while
    ``has_work()``) → per-round ``EngineEvent`` lists. ``abort(rid)`` cancels
    a request at any stage, releasing its KV pages / slot immediately.
    ``serve(requests)`` is the offline compatibility wrapper over the same
    loop (identical greedy tokens, identical readback count).

    ``cache_mode``: ``"paged"`` | ``"slot"`` | ``"auto"`` (paged where the
    architecture supports it — see ``supports_paged_cache``).
    ``overlap``: paged mode only — run the one-step-lookahead pipeline
    (default). ``False`` syncs every round immediately with per-row token
    transfers, reproducing the pre-zero-sync hot path for profiling.
    ``mesh``: paged mode only — run the fused steps sharded (see the module
    docstring); ``None`` is the exact single-device engine. Slot mode
    ignores it (recurrent/MLA archs stay single-device).
    ``prefix_cache``: paged mode only — reuse frozen full pages across
    requests sharing a token prefix (system prompts, multi-turn). Admission
    consults ``BlockAllocator.match_prefix`` and prefill starts *after* the
    matched prefix; fully-written pages are committed (frozen) into the
    content index as prefill/decode advances. Greedy tokens are bit-identical
    with the cache on or off — cached K/V pages hold exactly the values
    recompute would produce (K/V are per-token projections, independent of
    chunking), so only the amount of prefill work changes.
    """

    def __init__(self, cfg: ModelConfig, scheduler: SchedulerBase, *,
                 cache_mode: str = "auto",
                 max_slots: int = 8, max_len: int = 512,
                 kv_capacity_tokens: Optional[int] = None,
                 page_size: int = 16, decode_reserve_tokens: int = 64,
                 overlap: bool = True, mesh=None, prefix_cache: bool = True,
                 defer_shared: bool = True,
                 spec_k: int = 0, drafter: Optional[DrafterBase] = None,
                 spec_class_caps: Optional[Dict[int, int]] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sample_seed: int = 0,
                 rctx: Optional[RunCtx] = None, seed: int = 0):
        if cache_mode == "auto":
            cache_mode = "paged" if supports_paged_cache(cfg) else "slot"
        if cache_mode == "paged" and not supports_paged_cache(cfg):
            raise ValueError(
                f"{cfg.name}: paged KV requires pure-attention mixers; "
                f"use cache_mode='slot'")
        self.cache_mode = cache_mode
        self.cfg = cfg
        self.sched = scheduler
        self.max_slots = max_slots
        self.max_len = max_len
        self.overlap = overlap
        self.rctx = rctx or RunCtx(block_q=32, block_k=32, mlstm_block=32)
        # the mesh applies to the paged executor only; slot mode (recurrent /
        # MLA archs) stays single-device and quietly ignores an env override.
        self.mesh = mesh if cache_mode == "paged" else None
        self._repl: Optional[NamedSharding] = None
        self._cache_shardings = None
        self.params = init_params(cfg, jax.random.PRNGKey(seed))
        self.stats = EngineStats()
        self.spans = Spans()
        self._tokens_out: Dict[int, List[int]] = {}
        self._seen_shapes = set()
        self._resumed: set = set()    # evicted mid-decode; re-prefill, no emit
        self._round_calls = 0
        self._last_round_evictions = 0
        self._t0 = time.perf_counter()

        # ---- step-API state (the former serve()-loop locals) ----------------
        self._pending: List[Tuple[float, int, Request]] = []  # future arrivals
        self._seq = 0                                  # heap tie-break counter
        self._queued: collections.deque = collections.deque()  # arrived, no KV
        self._active: List[Request] = []                        # KV-resident
        self._done: List[Request] = []                          # FINISHED
        self._aborted: List[Request] = []                       # ABORTED
        self._prompts: Dict[int, np.ndarray] = {}
        self._reqs: Dict[int, Request] = {}     # rid -> live (unretired) req
        self._events: List[EngineEvent] = []
        self._progress = "idle"   # what the last step() did: "executed" |
                                  # "empty" | "no-decision" | "idle"
        self._inflight: Optional[_InflightRound] = None

        # ---- speculative decoding + sampling policy --------------------------
        # spec_k > 0 turns decode-eligible rows into multi-token verify rows
        # (paged mode only; slot mode ignores it). Per-class caps bound the
        # draft budget by SLO-class rank; interactive (rank 0) drops to plain
        # decode under KV pressure — verify rows cost fixed compute for a
        # variable token yield, exactly the trade a latency-critical class
        # should not make when the system is already strained.
        self.spec_k = int(spec_k) if cache_mode == "paged" else 0
        self.drafter = drafter or (NGramDrafter() if self.spec_k else None)
        self.spec_class_caps = dict(spec_class_caps or {})
        self.sampling = (Sampling(temperature=temperature, top_k=top_k,
                                  seed=sample_seed)
                         if temperature > 0 else None)
        self._sample_nonce = 0          # monotonic per-dispatch RNG fold
        self._round_spec_rids: set = set()
        self._spec_acc_mean = 0.0       # EMA of accepted length per verify row
        self._spec_acc_m2 = 0.0         # EMA of its square (for the std)
        self._spec_draft_ema = 0.0      # EMA of drafts per decode-eligible row

        self.prefix_cache = bool(prefix_cache) and cache_mode == "paged"
        # dependency-aware admission defer (in-flight burst sharing): when K
        # concurrent requests share an uncommitted prefix, followers wait for
        # the leader to commit the shared pages instead of prefilling the
        # prefix K times (sglang-style). Only meaningful with the prefix
        # cache on — without an index there is nothing to wait for.
        self.defer_shared = bool(defer_shared) and self.prefix_cache
        self._defer_rounds: Dict[int, int] = {}   # rid -> rounds deferred
        self._defer_cap = 512                     # livelock safety valve
        if cache_mode == "paged":
            capacity = kv_capacity_tokens or max_slots * max_len
            self.alloc = BlockAllocator(capacity, page_size)
            self.page_size = page_size
            self.decode_reserve = decode_reserve_tokens
            # one extra physical page (the last) is the trash page: padding
            # tokens' KV writes land there and are never read back.
            self.cache = init_paged_cache(cfg, self.alloc.num_blocks + 1,
                                          page_size)
            self._trash_slot = self.alloc.num_blocks * page_size
            self._length: Dict[int, int] = {}     # tokens resident per rid
            self._folded: Dict[int, int] = {}     # gen tokens folded on evict
            self._dev_cache: Dict[Tuple, Tuple[np.ndarray, jnp.ndarray]] = {}
            jit_kw = {}
            if self.mesh is not None:
                self._init_mesh_state(cfg)
                # pin the outputs: token ids replicated (the one host-visible
                # artifact per round), cache exactly on the input shardings so
                # donation stays a same-layout buffer reuse.
                jit_kw["out_shardings"] = (self._repl, self._cache_shardings)
            rctx_ = self.rctx
            sampling = self.sampling
            # ``nonce`` is a traced int32 scalar so changing it never
            # retraces; with greedy sampling it is dead code and XLA drops it.

            def chunk_fused(params, tokens, cache, row_pos, row_lens, bt, ws,
                            logits_at, nonce):
                return paged_chunk_step(cfg, params, tokens, cache, row_pos,
                                        rctx=rctx_, row_lens=row_lens,
                                        block_tables=bt, write_slots=ws,
                                        logits_at=logits_at,
                                        sampling=sampling, nonce=nonce)

            def decode_fused(params, tokens, cache, lengths, bt, ws, nonce):
                return paged_decode_step(cfg, params, tokens, cache,
                                         rctx=rctx_, lengths=lengths,
                                         block_tables=bt, write_slots=ws,
                                         sampling=sampling, nonce=nonce)

            def spec_fused(params, tokens, cache, row_pos, row_lens, bt, ws,
                           nonce):
                return paged_spec_step(cfg, params, tokens, cache, row_pos,
                                       rctx=rctx_, row_lens=row_lens,
                                       block_tables=bt, write_slots=ws,
                                       sampling=sampling, nonce=nonce)

            self._jit_chunk_fused = jax.jit(chunk_fused, donate_argnums=(2,),
                                            **jit_kw)
            self._jit_decode_fused = jax.jit(decode_fused, donate_argnums=(2,),
                                             **jit_kw)
            self._jit_spec_fused = jax.jit(spec_fused, donate_argnums=(2,),
                                           **jit_kw)
        else:
            self._init_slot_mode(cfg, max_slots, max_len)

    # =========================================================================
    # sharded paged executor (jit + shard_map on a mesh)
    # =========================================================================
    def _init_mesh_state(self, cfg: ModelConfig) -> None:
        """Place the paged model state on the mesh: params and host-derived
        operands replicate (dense math is identical on every device — the
        bit-identity guarantee), while the KV page pools shard attention
        heads on the ``model`` axis when the head count divides it
        (``launch/sharding.py``'s cache rule; otherwise the pools stay
        replicated and the attention ops sequence-shard the computation).
        The scheduler stack never sees any of this — block tables and token
        ids stay replicated host-side state."""
        from repro.launch.sharding import paged_cache_specs
        mesh = self.mesh
        axis = self.rctx.shard_axis
        self.rctx = dataclasses.replace(self.rctx, mesh=mesh)
        self._repl = NamedSharding(mesh, P())
        self.params = jax.device_put(self.params, self._repl)
        shapes = jax.eval_shape(lambda c: c, self.cache)
        specs = paged_cache_specs(cfg, shapes, mesh, axis=axis)
        self._cache_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        self.cache = jax.tree.map(jax.device_put, self.cache,
                                  self._cache_shardings)

    def _to_dev(self, arr) -> jnp.ndarray:
        """Host->device upload; replicated across the mesh when sharded (the
        engine's host state — tokens, tables, slots — is mesh-invariant)."""
        if self._repl is not None:
            return jax.device_put(arr, self._repl)
        return jnp.asarray(arr)

    def kv_shards(self) -> int:
        """How many ways the KV page pools are partitioned (1 = replicated
        or single-device); the shared ``head_shards`` rule, so this always
        agrees with cache placement and ops dispatch."""
        from repro.kernels.shard_utils import head_shards
        if self.cache_mode != "paged" or self.mesh is None:
            return 1
        return head_shards(self.cfg.num_kv_heads, self.mesh,
                           self.rctx.shard_axis)

    def shard_info(self) -> Dict:
        """Mesh + per-shard KV-pool accounting (BENCH_goodput.json record)."""
        if self.cache_mode != "paged":
            return {"mesh": None, "kv_partition": "none", "kv_shards": 1}
        mesh = self.mesh
        shards = self.kv_shards()
        m = 1 if mesh is None else int(mesh.shape.get(self.rctx.shard_axis, 1))
        # a 1-wide (or absent) shard axis runs the exact single-device
        # dispatch — report it as unpartitioned, not as a trivial head shard.
        if shards > 1:
            partition = "heads"
        elif m > 1:
            partition = "sequence"
        else:
            partition = "none"
        info = {
            "mesh": None if mesh is None else "x".join(
                str(mesh.shape[a]) for a in mesh.axis_names),
            "axes": None if mesh is None else dict(mesh.shape),
            "kv_partition": partition,
            "kv_shards": shards,
            "kv_heads_per_shard": self.cfg.num_kv_heads // shards,
        }
        info.update(self.alloc.shard_stats(shards))
        return info

    def shard_banner(self) -> str:
        """One-line human-readable form of :meth:`shard_info` (the serving
        entrypoints print it instead of each formatting their own)."""
        info = self.shard_info()
        return (f"sharded paged executor: mesh={info['mesh']} "
                f"kv_partition={info['kv_partition']} "
                f"shards={info['kv_shards']}")

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def now(self) -> float:
        """Seconds on the engine clock (what event timestamps and request
        arrivals are measured against)."""
        return self._now()

    # =========================================================================
    # step API: add_request / abort / step / has_work
    # =========================================================================
    @staticmethod
    def _bump(d: Dict[str, int], cls: str) -> None:
        d[cls] = d.get(cls, 0) + 1

    def _event(self, kind: EventKind, rid: int, t: float,
               token: Optional[int] = None, reason: str = "") -> None:
        self._events.append(EngineEvent(kind, rid, t, token, reason))

    def _drain_events(self) -> List[EngineEvent]:
        evts, self._events = self._events, []
        return evts

    def add_request(self, req: Request, prompt: Sequence[int]) -> None:
        """Hand a request to the engine. ``req.arrival`` is on the engine
        clock: a future arrival is held back (the offline wrapper's replayed
        traces), a past/now arrival joins the admission queue immediately."""
        assert req.rid not in self._reqs, f"duplicate rid {req.rid}"
        # transcripts (_tokens_out) outlive retirement — serve() exposes
        # them — so recycling a finished request's rid would splice two
        # streams together (and feed the old stream's last token into the
        # new stream's first decode). Fail loudly instead.
        assert req.rid not in self._tokens_out, \
            f"rid {req.rid} reuses a finished request's id on this engine"
        self._reqs[req.rid] = req
        self._prompts[req.rid] = np.asarray(prompt, np.int32)
        if req.arrival > self._now():
            heapq.heappush(self._pending, (req.arrival, self._seq, req))
            self._seq += 1
        else:
            self._queued.append(req)
            self._event(EventKind.QUEUED, req.rid, self._now())

    def abort(self, rid: int) -> List[EngineEvent]:
        """Cancel a request at any stage: drop it from the arrival/admission
        queues, or free its KV pages / slot if it is mid-prefill or
        mid-decode. Returns the events this produced (the in-flight round is
        flushed first when it references the request, so its final TOKEN
        events surface here too)."""
        r = self._reqs.get(rid)
        if r is None or r.state in (ReqState.FINISHED, ReqState.ABORTED):
            return []
        if any(e[2].rid == rid for e in self._pending):
            self._pending = [e for e in self._pending if e[2].rid != rid]
            heapq.heapify(self._pending)
        try:
            self._queued.remove(r)
        except ValueError:
            pass
        if r in self._active:
            # settle the in-flight round first when it will *emit* for this
            # request (this is that round's one readback happening early, not
            # an extra sync). A non-emitting row — mid-prefill, or a WAITING
            # request with no row at all — needs no flush: its page writes
            # land before any later owner of the pages writes them.
            fr = self._inflight
            if fr is not None and (any(x == rid for x, _ in fr.emits)
                                   or any(s[0] == rid for s in fr.spec_emits)):
                self._flush_round()
                if r.state == ReqState.FINISHED:  # the flush finished it (stop)
                    return self._drain_events()
        r.state = ReqState.ABORTED
        r.finish_time = self._now()
        self._retire(r)
        self._aborted.append(r)
        self.stats.aborted += 1
        self._bump(self.stats.aborted_by_class, r.slo_class)
        self._event(EventKind.ABORTED, rid, self._now())
        return self._drain_events()

    def has_work(self) -> bool:
        """True while any request is pending/queued/active or a dispatched
        round still awaits its readback (the final tokens)."""
        return bool(self._pending or self._queued or self._active
                    or self._inflight is not None)

    @property
    def progress(self) -> str:
        """What the last ``step()`` accomplished: ``"executed"`` (a round
        ran), ``"empty"`` (decision evicted away), ``"no-decision"``, or
        ``"idle"`` — drivers use this to pace sleeps and detect wedges."""
        return self._progress

    def next_arrival(self) -> Optional[float]:
        """Engine-clock time of the earliest not-yet-due request, or None.
        Idle drivers sleep until this instead of polling."""
        return self._pending[0][0] if self._pending else None

    @property
    def queue_depth(self) -> int:
        """Requests that have arrived but hold no KV yet (admission queue)."""
        return len(self._queued)

    def outstanding_tokens(self) -> int:
        """Token-work the engine still owes across every live request:
        uncomputed prompt tokens plus remaining output budget. This is the
        router's load signal — queue depth weighted by per-request estimated
        cost — so it counts queued *and* active requests (queued work is
        exactly what a newly routed request would wait behind)."""
        tot = 0
        for r in self._reqs.values():
            if r.state in (ReqState.FINISHED, ReqState.ABORTED):
                continue
            tot += r.remaining_prefill() + max(r.max_output - r.generated, 0)
        return tot

    def class_queue_depth(self, max_rank: int) -> int:
        """Live requests at SLO-class rank ``max_rank`` or more critical —
        the work a new request of that rank would queue behind (the router's
        class-aware tie-break: interactive must not queue behind batch)."""
        return sum(1 for r in self._reqs.values()
                   if r.state not in (ReqState.FINISHED, ReqState.ABORTED)
                   and r.class_rank() <= max_rank)

    @property
    def last_round_evictions(self) -> int:
        """Evictions the most recent executed round caused (wedge guards use
        this: an empty round that also evicted nothing cannot make progress
        by itself)."""
        return self._last_round_evictions

    def stalled(self) -> bool:
        """Wedge predicate shared by every driver: the last ``step()`` made
        no progress and nothing external will change that — an empty round
        that evicted nothing (a request outgrew total capacity), or an idle
        engine holding queued-but-unadmittable work with no future arrivals.
        Drivers bail after a few consecutive True results instead of
        spinning to their wall clock."""
        if self._progress == "empty" and self._last_round_evictions == 0:
            return True
        return (self._progress == "idle" and not self._pending
                and bool(self._queued))

    def flush(self) -> List[EngineEvent]:
        """Settle any in-flight round now (its one readback happens early,
        not extra) and return the events that surfaced. Drivers call this on
        abnormal exits (wall budget, wedge) so the final round's tokens are
        never stranded on device."""
        self._flush_round()
        return self._drain_events()

    def _retire(self, r: Request) -> None:
        """Release a request's execution resources (idempotent)."""
        if self.cache_mode == "paged":
            if r.rid in self.alloc.owners:
                self.alloc.free(r.rid)
            self._length.pop(r.rid, None)
            self._folded.pop(r.rid, None)
        else:
            self._release_slot(r)
        self._resumed.discard(r.rid)
        self._defer_rounds.pop(r.rid, None)
        if r in self._active:
            self._active.remove(r)
        self._reqs.pop(r.rid, None)
        # drop the prompt array — the dominant per-request memory. Token
        # transcripts (_tokens_out) and the _done list are intentionally
        # kept: serve()'s return contract exposes them after retirement.
        self._prompts.pop(r.rid, None)

    # ---- in-flight burst sharing (dependency-aware admission defer) ----------
    def _shared_whole_pages(self, a: np.ndarray, b: np.ndarray) -> int:
        """Whole pages of common prefix between two token arrays."""
        ps = self.page_size
        n = min(len(a), len(b)) // ps * ps
        if n == 0:
            return 0
        eq = a[:n] == b[:n]
        if eq.all():
            return n // ps
        return int(np.argmin(eq)) // ps

    def _defer_for_leader(self, r: Request) -> bool:
        """True when admitting ``r`` *now* would recompute a prefix that an
        in-flight leader is about to commit: some active request shares more
        whole prompt pages with ``r`` than the index can serve yet, and its
        commit pointer is still advancing toward them. Deferring the
        follower one round converts K concurrent prefills of a shared burst
        prefix into one prefill plus K-1 cache hits. The wait is bounded:
        the leader either commits the pages (the index match then covers
        them and the gain vanishes), or stops being eligible (finished /
        evicted / commit-stalled), or the per-rid round cap fires."""
        if not self.defer_shared:
            return False
        prompt = self._prompts[r.rid]
        # page-granular cap mirroring admission's match_limit: the last
        # prompt token is always computed, so pages past it can't be reused.
        cap = (r.prompt_len - 1) // self.page_size * self.page_size
        if cap == 0:
            return False
        matched_now = self.alloc.match_prefix(prompt,
                                              max_tokens=r.prompt_len - 1)[1]
        gain = 0
        for lead in self._active:
            if lead.state == ReqState.DECODING:
                continue    # prompt pages already committed (or stalled)
            if (lead.rid not in self.alloc.owners
                    or self.alloc.commit_stalled(lead.rid)):
                continue
            lp = self._prompts.get(lead.rid)
            if lp is None:
                continue
            shared = min(self._shared_whole_pages(prompt, lp)
                         * self.page_size, cap)
            if (shared > matched_now
                    and self.alloc.committed_count(lead.rid)
                    * self.page_size < shared):
                gain = max(gain, shared - matched_now)
        if gain >= self.page_size \
                and self._defer_rounds.get(r.rid, 0) < self._defer_cap:
            self._defer_rounds[r.rid] = self._defer_rounds.get(r.rid, 0) + 1
            self.stats.deferred_admissions += 1
            return True
        return False

    def _admit(self) -> List[int]:
        """Move due arrivals into the admission queue, then admit while the
        free pool lasts (full-prompt + decode-reserve reservation). Admission
        order weights the request's named SLO class: latency-critical classes
        (``interactive``) go first, FIFO preserved within a class — a
        single-class workload therefore admits in exactly the legacy FIFO
        order (the stable sort is a no-op). Returns the admitted rids."""
        paged = self.cache_mode == "paged"
        admitted: List[int] = []
        while self._pending and self._pending[0][0] <= self._now():
            _, _, r = heapq.heappop(self._pending)
            self._queued.append(r)
            self._event(EventKind.QUEUED, r.rid, r.arrival)
        # O(1) short-circuit: with the free pool exhausted no admission can
        # succeed, so skip the scan entirely (the common state while
        # saturated — this is what keeps admission off the hot path).
        exhausted = (self.alloc.free_blocks == 0 if paged
                     else not self.free_slots)
        if self._queued and not exhausted:
            if len(self._queued) > 1:
                self._queued = collections.deque(
                    sorted(self._queued, key=lambda r: r.class_rank()))
            failures = 0
            for _ in range(len(self._queued)):
                r = self._queued.popleft()
                if paged and self._defer_for_leader(r):
                    # burst sharing: wait for the in-flight leader's commit
                    # instead of prefilling the shared prefix again.
                    self._queued.append(r)
                    failures += 1
                    continue
                if paged:
                    # admission *reserves* the full prompt + decode headroom
                    # so concurrent admits are gated by the same free pool
                    # (admit(rid, 0) would let every fitting prompt in at
                    # once and convert admission control into evict thrash).
                    # With the prefix cache on, frozen pages matching the
                    # prompt are reused in place of fresh allocations — the
                    # match is capped at prompt_len - 1 so at least one
                    # prompt token is always computed for first-token logits.
                    need = r.remaining_prefill()
                    ok = self.alloc.admit(
                        r.rid, need + self.decode_reserve,
                        token_ids=(self._prompts[r.rid]
                                   if self.prefix_cache else None),
                        match_limit=r.prompt_len - 1)
                else:
                    ok = self._assign_slot(r) is not None
                if ok:
                    self._active.append(r)
                    self._defer_rounds.pop(r.rid, None)
                    if paged:
                        matched = self.alloc.cached_tokens(r.rid)
                        self._length[r.rid] = matched
                        if matched:
                            # prefill resumes after the frozen prefix: the
                            # whole scheduler stack (remaining_prefill,
                            # predictor features, chunk budgets) sees only
                            # the uncached remainder, while context_len
                            # still counts the reused tokens.
                            r.prefilled = matched
                            r.cached_prefix = matched
                        self.stats.cache_hit_tokens += matched
                        self.stats.prompt_tokens += r.prompt_len
                    self._event(EventKind.ADMITTED, r.rid, self._now())
                    admitted.append(r.rid)
                else:
                    self._queued.append(r)
                    failures += 1
                if paged and self.alloc.free_blocks == 0:
                    # pool just drained: rotate the failures back to the
                    # front so FIFO order survives the early exit.
                    self._queued.rotate(failures)
                    break
        self.stats.max_concurrency = max(self.stats.max_concurrency,
                                         len(self._active))
        return admitted

    def step(self) -> List[EngineEvent]:
        """Run one scheduler round: admit, schedule, dispatch (≤2 fused model
        calls in paged mode), and flush the *previous* round's deferred
        readback. Returns the events that settled during this call; an idle
        step (nothing admitted or schedulable) flushes any in-flight round so
        the final tokens always surface. The round is the span
        ``engine.round``; its args count live KV pages, queued and active
        requests as it ends."""
        self.spans.round += 1
        with self.spans.span("engine.round", round=self.spans.round) as sp:
            events = self._step()
            if self.cache_mode == "paged":
                sp.args["live_pages"] = self.alloc.live_blocks
            sp.args["queued"] = len(self._queued)
            sp.args["active"] = len(self._active)
        return events

    def _step(self) -> List[EngineEvent]:
        paged = self.cache_mode == "paged"
        with self.spans.span("engine.admit") as sp:
            sp.args["rids"] = self._admit()
        if not self._active:
            self._flush_round()         # device is idle anyway
            self._progress = "idle"
            return self._drain_events()

        # admitted-but-unstarted requests are offered as ``waiting`` so MLPS
        # ordering applies to them (they are executable immediately).
        waiting = [r for r in self._active if r.state == ReqState.WAITING]
        prefilling = [r for r in self._active
                      if r.state == ReqState.PREFILLING]
        decoding = [r for r in self._active if r.state == ReqState.DECODING]
        kv = self._kv_pressure() if paged else None
        with self.spans.span("engine.schedule"):
            decision = self.sched.schedule(self._now(), waiting, prefilling,
                                           decoding, kv=kv)
        if decision is None:
            self._flush_round()
            self._progress = "no-decision"
            return self._drain_events()

        self._round_calls = 0
        it0 = time.perf_counter()
        executed = (self._execute_paged(decision) if paged
                    else self._execute_slot(decision))
        if not executed:
            # every entry was evicted away (severe KV pressure): the driver
            # should yield so re-admission can make progress.
            self._flush_round()
            self._progress = "empty"
            return self._drain_events()
        self._progress = "executed"
        latency = time.perf_counter() - it0
        t_now = self._now()
        self.stats.iterations += 1
        self.stats.max_round_calls = max(self.stats.max_round_calls,
                                         self._round_calls)

        executed_batch = []
        stamped = []
        for entry in executed:
            r, n, ctx = entry[0], entry[1], entry[2]
            drafts = entry[3] if len(entry) > 3 else 0
            if r.state in (ReqState.FINISHED, ReqState.ABORTED):
                # finished by the flush inside execute (stop token): its row
                # this round was dead — nothing to advance or emit.
                continue
            # verify rows observe as (tokens, ctx, draft_tokens) triples so
            # the predictor prices their extra per-row work (features x8).
            executed_batch.append((n, ctx, drafts) if drafts else (n, ctx))
            emitted = False
            was_first = r.first_token_time is None
            if r.state == ReqState.DECODING:
                if paged and r.rid in self._round_spec_rids:
                    # speculative verify row: how many tokens it emits is
                    # decided on device; emission, stop handling and page
                    # rollback all happen at the flush, from the payload.
                    continue
                r.emit_token(t_now)
                self.stats.decode_tokens += 1
                emitted = True
            else:
                r.advance_prefill(n)
                if r.remaining_prefill() == 0:
                    if r.rid in self._resumed:
                        # re-prefill after eviction: the pending token was
                        # already emitted; resume decoding silently.
                        self._resumed.discard(r.rid)
                        r.state = ReqState.DECODING
                    else:
                        r.emit_token(t_now)
                        emitted = True
            if emitted:
                if paged:
                    # token value is on device; events settle at the flush.
                    stamped.append((r, len(r.token_times) - 1, was_first,
                                    r.state == ReqState.FINISHED))
                else:
                    # slot mode syncs per round: ids are host-visible now.
                    tok = self._tokens_out[r.rid][-1]
                    reason = "length"
                    if r.state != ReqState.FINISHED and r.hits_stop(tok):
                        r.state = ReqState.FINISHED
                        r.finish_time = t_now
                        reason = "stop"
                    self._event(EventKind.FIRST_TOKEN if was_first
                                else EventKind.TOKEN, r.rid, t_now, token=tok)
                    if r.state == ReqState.FINISHED:
                        self._event(EventKind.FINISHED, r.rid, t_now,
                                    reason=reason)
            if r.state == ReqState.FINISHED:
                self._retire(r)
                self._done.append(r)
                self._bump(self.stats.finished_by_class, r.slo_class)
        if paged:
            # readback + observe happen at the next round's flush; the
            # executed batch is recorded on the in-flight round so the
            # observation reflects what actually ran (post split/clamp).
            if self._inflight is not None:
                self._inflight.executed_batch = executed_batch
                self._inflight.stamped = stamped
            self.alloc.check_invariants()
            if not self.overlap:
                self._flush_round()
        else:
            # close the loop on what actually ran (post split/clamp), not
            # on what the decision asked for.
            self.sched.observe(executed_batch, latency, kv=None)
        return self._drain_events()

    # =========================================================================
    # slot mode (legacy contiguous rows; recurrent / MLA / enc-dec archs)
    # =========================================================================
    def _init_slot_mode(self, cfg: ModelConfig, max_slots: int, max_len: int):
        rctx = self.rctx
        self.cache = init_cache(cfg, max_slots, max_len)
        self.lengths = np.zeros((max_slots,), np.int32)   # cached tokens/slot
        self.slot_of: Dict[int, int] = {}
        self.free_slots = list(range(max_slots))
        self._jit_chunk = {}

        def decode_merged(params, tokens, cache, lengths_p1, keep_mask):
            # run one decode step for every slot, then keep the updated cache
            # only for rows that are really decoding (others' recurrent
            # state / KV must not be touched by their padding tokens)
            logits, new_cache = decode_step(cfg, params, tokens, cache, 0,
                                            rctx=rctx, lengths=lengths_p1)
            def merge(new, old):
                m = keep_mask.reshape((1, -1) + (1,) * (new.ndim - 2))
                return jnp.where(m, new, old)
            merged = jax.tree.map(merge, new_cache, cache)
            return logits, merged

        self._jit_decode = jax.jit(decode_merged, donate_argnums=(2,))

        def chunk_one(params, tokens, cache, start, slot, last_idx):
            # slice out the slot's cache row, run the chunk at offset
            # ``start``, and write the row back — other slots untouched.
            sub = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, 1), cache)
            logits, new_sub = chunk_prefill_step(cfg, params, tokens, sub,
                                                 start, rctx=rctx,
                                                 logits_at=last_idx)
            merged = jax.tree.map(
                lambda full, row: jax.lax.dynamic_update_slice_in_dim(
                    full, row.astype(full.dtype), slot, 1),
                cache, new_sub)
            return logits, merged

        self._chunk_one = chunk_one

    def _assign_slot(self, req: Request) -> Optional[int]:
        if req.rid in self.slot_of:
            return self.slot_of[req.rid]
        if not self.free_slots:
            return None
        s = self.free_slots.pop()
        self.slot_of[req.rid] = s
        self.lengths[s] = 0
        return s

    def _release_slot(self, req: Request) -> None:
        s = self.slot_of.pop(req.rid, None)
        if s is not None:
            self.free_slots.append(s)

    def _chunk_fn(self, chunk_len: int):
        key = chunk_len
        if key not in self._jit_chunk:
            self._jit_chunk[key] = jax.jit(self._chunk_one,
                                           donate_argnums=(2,))
            self.stats.compiled_shapes += 1
        return self._jit_chunk[key]

    def _run_prefill_chunk(self, req: Request, n: int,
                           prompt_tokens: np.ndarray) -> int:
        """Execute up to ``n`` prompt tokens; returns tokens actually run.
        Allocations above the top bucket are split across dispatches (never
        silently truncated — the caller advances by the returned count)."""
        slot = self.slot_of[req.rid]
        total = min(n, req.prompt_len - int(self.lengths[slot]))
        recurrent = any(k in (MAMBA, MLSTM, SLSTM)
                        for k in self.cfg.layer_pattern)
        done = 0
        while done < total:
            start = int(self.lengths[slot])
            step = min(total - done, CHUNK_BUCKETS[-1])
            # recurrent state advances per token, so padding tokens would
            # pollute it — recurrent archs use exact-length chunks (more JIT
            # shapes, fine)
            blen = step if recurrent else _bucket(step)
            chunk = np.zeros((1, blen), np.int32)
            real = prompt_tokens[start:start + step]
            chunk[0, :step] = real
            # bucket padding: repeat the last real token (masked out afterwards
            # by restoring the true length; attention past ``start+blen`` is
            # causal)
            if step < blen and step > 0:
                chunk[0, step:] = real[-1]
            fn = self._chunk_fn(blen)
            logits, self.cache = fn(self.params, jnp.asarray(chunk),
                                    self.cache, start, slot, step - 1)
            self.lengths[slot] = start + step
            self.stats.prefill_calls += 1
            self._round_calls += 1
            done += step
        if int(self.lengths[slot]) >= req.prompt_len and done > 0:
            tok = int(jnp.argmax(logits[0]))
            self._tokens_out.setdefault(req.rid, []).append(tok)
        return done

    def _run_decode_slot(self, reqs: Sequence[Request]) -> None:
        tokens = np.zeros((self.max_slots, 1), np.int32)
        keep = np.zeros((self.max_slots,), bool)
        for r in reqs:
            slot = self.slot_of[r.rid]
            prev = self._tokens_out.get(r.rid, [0])
            tokens[slot, 0] = prev[-1] if prev else 0
            keep[slot] = True
        lengths_p1 = self.lengths + 1   # every row writes to its empty spot
        logits, self.cache = self._jit_decode(
            self.params, jnp.asarray(tokens), self.cache,
            jnp.asarray(lengths_p1), jnp.asarray(keep))
        for r in reqs:
            slot = self.slot_of[r.rid]
            self.lengths[slot] += 1
            tok = int(jnp.argmax(logits[slot]))
            self._tokens_out.setdefault(r.rid, []).append(tok)
        self.stats.decode_calls += 1
        self._round_calls += 1

    # =========================================================================
    # paged mode: allocator-backed admission / growth / eviction
    # =========================================================================
    def _kv_pressure(self) -> KVPressure:
        """Snapshot for the scheduler; ``evictions`` reports the *previous*
        round's churn (the signal to shrink the next budget).

        Pressure is measured against tokens actually *written* to the cache,
        not against block reservations: admission already reserves each
        prompt, so reserved-but-uncomputed space is precisely what scheduled
        prefill tokens consume — counting it as used would throttle chunk
        budgets exactly when there is nothing to protect.

        With the prefix cache, shared pages are counted once however many
        owners reference them (each frozen live page holds exactly
        ``page_size`` written tokens; an owner's private remainder is its
        resident length minus its frozen prefix), and refcount-0 cached
        pages count as *reclaimable* free space — live pressure must not
        back budgets off just because the reclaimable cache is warm."""
        capacity = self.alloc.num_blocks * self.page_size
        ps = self.page_size
        computed = ps * self.alloc.referenced_committed_blocks() + sum(
            max(self._length.get(rid, 0) - ps * self.alloc.committed_count(rid),
                0)
            for rid in self.alloc.owners)
        return KVPressure(utilization=computed / capacity,
                          free_tokens=capacity - computed,
                          reclaimable_tokens=self.alloc.cached_blocks * ps,
                          evictions=self._last_round_evictions)

    # ---- prefix-cache plumbing ----------------------------------------------
    def _content_upto(self, rid: int, upto: int) -> np.ndarray:
        """Token content of ``rid``'s first ``upto`` cache positions: the
        (possibly eviction-grown) prompt, then emitted tokens from the
        folded offset on — exactly what the dispatched writes put there."""
        prompt = self._prompts[rid]
        if upto <= len(prompt):
            return prompt[:upto]
        gen = self._tokens_out.get(rid, [])
        folded = self._folded.get(rid, 0)
        tail = np.asarray(gen[folded:folded + upto - len(prompt)], np.int32)
        return np.concatenate([prompt, tail])

    def _commit(self, rid: int, upto: Optional[int] = None) -> None:
        """Freeze ``rid``'s fully-written pages into the content index (a
        no-op until the resident length crosses the next page boundary).
        Called only after the covering writes were dispatched: any future
        reader matches the pages in a *later* dispatch, so device-order
        guarantees it sees the written content. ``upto`` caps the freeze
        below the resident length — speculative verify rows write k
        unconfirmed draft positions that must never freeze (a rejected tail
        is overwritten next round, and frozen pages may already be shared)."""
        if not self.prefix_cache or rid not in self.alloc.owners:
            return
        if upto is None:
            upto = self._length.get(rid, 0)
        if (upto // self.page_size > self.alloc.committed_count(rid)
                and not self.alloc.commit_stalled(rid)):
            self.alloc.commit(rid, self._content_upto(rid, upto), upto)

    def cache_info(self) -> Dict:
        """Prefix-cache hit/commit accounting (BENCH_goodput.json record)."""
        st = self.stats
        info = {"prefix_cache": self.prefix_cache,
                "hit_tokens": st.cache_hit_tokens,
                "prompt_tokens": st.prompt_tokens,
                "hit_rate": st.cache_hit_tokens / max(st.prompt_tokens, 1),
                "prefill_tokens_computed": st.prefill_tokens}
        if self.cache_mode == "paged":
            info.update(self.alloc.cache_stats())
        return info

    # ---- speculative decoding plumbing --------------------------------------
    def _transcript(self, rid: int) -> np.ndarray:
        """Full visible token history (prompt incl. eviction folds + emitted
        tail) — the drafter's lookup corpus. Host-visible only post-flush,
        which is why speculative rounds flush before assembly."""
        gen = self._tokens_out.get(rid, [])
        tail = gen[self._folded.get(rid, 0):]
        if not tail:
            return self._prompts[rid]
        return np.concatenate([self._prompts[rid],
                               np.asarray(tail, np.int32)])

    def _spec_pressure(self) -> bool:
        """Should latency-critical classes stop speculating? Mirrors the
        scheduler's budget-backoff signals: KV churn or near-full pool."""
        if self._last_round_evictions > 0:
            return True
        backoff = getattr(self.sched, "kv_backoff_util", 0.92)
        return self._kv_pressure().utilization > backoff

    def _propose_drafts(self, r: Request,
                        pressure: bool) -> Optional[np.ndarray]:
        """Draft tokens for one decode-eligible row, after policy caps:
        per-class ``spec_k`` budget, the request's remaining output budget
        (drafting past max_output is wasted verify compute), and the
        interactive-under-pressure opt-out. None -> plain decode row."""
        k = self.spec_class_caps.get(r.class_rank(), self.spec_k)
        k = min(k, self.spec_k, r.max_output - r.generated - 1)
        if k <= 0 or (pressure and r.class_rank() == 0):
            return None
        drafts = self.drafter.propose(self._transcript(r.rid), k)
        if drafts is None or len(drafts) == 0:
            return None
        return np.asarray(drafts[:k], np.int32)

    def _note_spec_accept(self, a: int) -> None:
        """EMA mean/second-moment of per-row accepted length; the std feeds
        the scheduler's TBT-risk shrink (forwarder ``spec_len_std``)."""
        beta = 0.9
        if self.stats.spec_rows <= 1:
            self._spec_acc_mean, self._spec_acc_m2 = float(a), float(a * a)
        else:
            self._spec_acc_mean = (beta * self._spec_acc_mean
                                   + (1 - beta) * a)
            self._spec_acc_m2 = beta * self._spec_acc_m2 + (1 - beta) * a * a

    def _feed_spec_signals(self, round_drafts: int, round_rows: int) -> None:
        """Publish speculation price signals to the scheduler's forwarder:
        expected drafts riding each decode row (what ``to_batch`` prices)
        and the accepted-length std (what the chunker treats as TBT risk)."""
        if round_rows <= 0:
            return
        beta = 0.8
        per_row = round_drafts / round_rows
        self._spec_draft_ema = (per_row if self.stats.spec_rounds <= 1
                                else beta * self._spec_draft_ema
                                + (1 - beta) * per_row)
        F = getattr(self.sched, "F", None)
        if F is not None and hasattr(F, "spec_draft_tokens"):
            F.spec_draft_tokens = self._spec_draft_ema
            var = max(self._spec_acc_m2 - self._spec_acc_mean ** 2, 0.0)
            F.spec_len_std = var ** 0.5

    def spec_info(self) -> Dict:
        """Speculation accounting (BENCH_goodput.json / CI smoke record)."""
        st = self.stats
        return {
            "spec_k": self.spec_k,
            "spec_rounds": st.spec_rounds,
            "verify_rows": st.spec_rows,
            "draft_tokens": st.spec_drafts,
            "accepted_tokens": st.spec_accepted,
            "acceptance_rate": st.spec_accepted / max(st.spec_drafts, 1),
            "emitted_tokens": st.spec_emitted,
            "tokens_per_verify_row": st.spec_emitted / max(st.spec_rows, 1),
            "decode_tokens_per_round": st.decode_tokens / max(st.iterations, 1),
        }

    def _evict(self, victim: Request) -> None:
        """Relegate ``victim`` (recompute-on-resume): drop its pages and fold
        already-emitted tokens into its prompt so re-prefill reconstructs the
        exact cache state and greedy decoding continues deterministically."""
        # folding reads the victim's emitted token *values*; if the previous
        # round is still in flight its ids are not host-visible yet — sync
        # early (this round's one readback just happens now instead of at
        # dispatch time; eviction is the rare path).
        self._flush_round()
        if victim.rid not in self.alloc.owners:
            return      # the flush just finished it (stop token) — no victim
        prompts = self._prompts
        self.alloc.evict(victim.rid)
        self.stats.evictions += 1
        self._bump(self.stats.evicted_by_class, victim.slo_class)
        self._event(EventKind.EVICTED, victim.rid, self._now())
        gen = self._tokens_out.get(victim.rid, [])
        if victim.generated > 0:
            # cache held prompt + gen[:-1] (the newest token was emitted but
            # not yet written back); that is exactly what re-prefill must
            # rebuild. The final emitted token stays pending as the next
            # decode input, so completion of the re-prefill must NOT emit.
            # ``_folded`` guards repeat evictions: tokens already folded into
            # the prompt by an earlier eviction must not be appended twice.
            folded = self._folded.get(victim.rid, 0)
            rebuild = np.asarray(gen[folded:victim.generated - 1], np.int32)
            if len(rebuild):
                prompts[victim.rid] = np.concatenate(
                    [prompts[victim.rid], rebuild])
                victim.prompt_len += len(rebuild)
            self._folded[victim.rid] = victim.generated - 1
            victim.recomputed = victim.generated - 1
            self._resumed.add(victim.rid)
        victim.prefilled = 0
        victim.cached_prefix = 0      # re-matched (if at all) at re-admission
        victim.state = ReqState.WAITING
        self._length.pop(victim.rid, None)
        if victim in self._active:
            self._active.remove(victim)
        self._queued.append(victim)

    def _grow_or_evict(self, req: Request, new_tokens: int,
                       protected: set) -> bool:
        """Grow ``req``'s allocation, evicting lowest-priority owners until
        it fits: prefer requests outside the current decision, then the least
        latency-critical SLO class, then the newest arrival. A victim of a
        *more* critical class than the needy request is never eligible —
        ``batch`` growth can never evict ``interactive`` (the starved request
        simply retries next round, after the critical owners finish).
        Returns False if capacity is exhausted even after evicting every
        eligible owner."""
        by_rid = {r.rid: r for r in self._active}
        needy_rank = req.class_rank()

        def rank_of(rid: int) -> int:
            r = by_rid.get(rid)
            return r.class_rank() if r is not None else needy_rank

        while not self.alloc.grow(req.rid, new_tokens):
            vid = self.alloc.pick_victim(
                req.rid,
                priority=lambda rid: (rid not in protected, rank_of(rid),
                                      by_rid[rid].arrival if rid in by_rid else 0.0),
                eligible=lambda rid: rank_of(rid) >= needy_rank)
            if vid is None or vid not in by_rid:
                return False
            self._evict(by_rid.pop(vid))
        return True

    # ---- zero-sync plumbing --------------------------------------------------
    def _readback(self, arr) -> np.ndarray:
        """The single device→host transfer point of the paged hot path (the
        transfer-counting test pins every other code path behind a
        ``transfer_guard``)."""
        self.stats.token_readbacks += 1
        with jax.transfer_guard_device_to_host("allow"):
            return np.asarray(arr)

    def _flush_round(self) -> None:
        """Materialize the in-flight round: one token-id readback, then append
        emitted ids to ``_tokens_out``, correct provisional timestamps to
        completion time, emit token events, decide stop-token termination
        (the ids are host-visible only here — EOS detection costs no extra
        sync), and feed the scheduler's observe()."""
        fr = self._inflight
        if fr is None:
            return
        self._inflight = None
        with self.spans.span("engine.flush"):
            self._settle_round(fr)

    def _settle_round(self, fr: _InflightRound) -> None:
        """The work of :meth:`_flush_round`, inside its span."""
        with self.spans.span("engine.readback") as sp:
            joined = (fr.toks[0] if len(fr.toks) == 1
                      else jnp.concatenate(fr.toks))
            if self.overlap:
                vals = self._readback(joined)
                toks = {idx: int(vals[idx]) for _, idx in fr.emits}
            else:
                # legacy profile: one scalar transfer per emitting row,
                # like the pre-zero-sync engine's per-row
                # ``int(jnp.argmax(logits[i]))`` (verify-row spans
                # transfer per row too in this mode).
                toks = {idx: int(self._readback(joined[idx]))
                        for _, idx in fr.emits}
                if fr.spec_emits:
                    vals = self._readback(joined)
        self.stats.sync_s += sp.dur_ns / 1e9
        t_done = self._now()
        by_rid = {r.rid: (r, k, wf, fin) for r, k, wf, fin in fr.stamped}
        for rid, idx in fr.emits:
            tok = toks[idx]
            self._tokens_out.setdefault(rid, []).append(tok)
            entry = by_rid.get(rid)
            if entry is None:
                continue
            r, k, was_first, was_finish = entry
            r.token_times[k] = t_done
            if was_first:
                r.first_token_time = t_done
            self._event(EventKind.FIRST_TOKEN if was_first
                        else EventKind.TOKEN, rid, t_done, token=tok)
            if was_finish:
                r.finish_time = t_done
                self._event(EventKind.FINISHED, rid, t_done, reason="length")
            elif r.state == ReqState.DECODING and r.hits_stop(tok):
                # stop-token termination, decided from the deferred readback.
                # The request may already sit in the next round's assembled
                # batch; that row executes dead (trash write, no emit).
                r.state = ReqState.FINISHED
                r.finish_time = t_done
                self._retire(r)
                self._done.append(r)
                self._bump(self.stats.finished_by_class, r.slo_class)
                self._event(EventKind.FINISHED, rid, t_done, reason="stop")
        for rid, base, Lb, n_real, start in fr.spec_emits:
            # payload span: [accepted, out_0 .. out_{Lb-1}]. The emitted
            # stream is out_0..out_a (a accepted drafts + the bonus token) —
            # exact autoregressive output, so greedy tokens are bit-identical
            # to plain decode at any k. Rejected tail KV sits in positions
            # start+m .. start+n_real-1 of already-owned pages; rolling the
            # resident length back makes the next round overwrite it.
            a = min(int(vals[base]), n_real - 1)
            outs = [int(v) for v in vals[base + 1:base + 2 + a]]
            r = self._reqs.get(rid)
            self.stats.spec_rows += 1
            self.stats.spec_drafts += n_real - 1
            self._note_spec_accept(a)
            if r is None or r.state in (ReqState.FINISHED, ReqState.ABORTED):
                continue        # aborted between dispatch and flush
            m = 0
            finished_reason = ""
            for tok in outs:
                self._tokens_out.setdefault(rid, []).append(tok)
                m += 1
                r.emit_token(t_done)
                self.stats.decode_tokens += 1
                self._event(EventKind.TOKEN, rid, t_done, token=tok)
                if r.state == ReqState.FINISHED:        # max_output reached
                    finished_reason = "length"
                    break
                if r.hits_stop(tok):
                    r.state = ReqState.FINISHED
                    finished_reason = "stop"
                    break
            self.stats.spec_accepted += a
            self.stats.spec_emitted += m
            if rid in self._length:
                self._length[rid] = start + m
            if finished_reason:
                r.finish_time = t_done
                self._retire(r)
                self._done.append(r)
                self._bump(self.stats.finished_by_class, r.slo_class)
                self._event(EventKind.FINISHED, rid, t_done,
                            reason=finished_reason)
        latency = time.perf_counter() - fr.t_dispatch
        self.stats.device_busy_s += latency
        self.sched.observe(fr.executed_batch, latency, kv=self._kv_pressure())

    def _upload_cached(self, kind, arr: np.ndarray) -> jnp.ndarray:
        """Host→device upload with content reuse: block tables are stable
        across steady decode rounds (they only change when a request crosses
        a page boundary or the batch recomposes), so the device buffer from
        the previous round is reused instead of re-uploaded. Keyed per
        consumer ``kind`` and per row-group, so the multiple same-shape
        dispatches of a split oversized round don't evict each other's
        entries within one round. The KV pool layout tag is part of the key:
        a stale buffer uploaded against a different physical page layout
        must never be reused (same table contents index different bytes)."""
        key = (kind, arr.shape, PAGED_KV_LAYOUT)
        prev = self._dev_cache.get(key)
        if prev is not None and np.array_equal(prev[0], arr):
            self.stats.reused_uploads += 1
            return prev[1]
        dev = self._to_dev(arr)
        self._dev_cache[key] = (arr, dev)
        return dev

    # ---- fused dispatch assembly ---------------------------------------------
    def _page_slots(self, rid: int, positions: np.ndarray) -> np.ndarray:
        pt = np.asarray(self.alloc.page_table(rid), np.int64)
        return pt[positions // self.page_size] * self.page_size \
            + positions % self.page_size

    def lengths_of(self, req: Request) -> int:
        return self._length.get(req.rid, 0)

    def _assemble_chunk(self, batch: List[Tuple[Request, int, int]],
                        prompts: Dict[int, np.ndarray]) -> dict:
        """Numpy assembly of one fused ragged-prefill dispatch (pure host
        work; runs while the previous round executes on device)."""
        R = len(batch)
        Rb = _row_bucket(R)
        Lb = _bucket(max(n for _, _, n in batch))
        nb = _pow2(max(self.alloc.blocks_for(s + n) for _, s, n in batch))
        tokens = np.zeros((Rb, Lb), np.int32)
        row_pos = np.zeros((Rb,), np.int32)
        row_lens = np.zeros((Rb,), np.int32)
        logits_at = np.zeros((Rb,), np.int32)
        tables = np.zeros((Rb, nb), np.int32)
        slots = np.full((Rb, Lb), self._trash_slot, np.int64)
        emit_rows: List[Tuple[int, int]] = []
        for i, (r, start, n) in enumerate(batch):
            tokens[i, :n] = prompts[r.rid][start:start + n]
            row_pos[i] = start
            row_lens[i] = start + n
            logits_at[i] = n - 1
            # the owner may hold pages beyond this dispatch's read range (a
            # split oversized chunk grows the whole allocation up front);
            # only the prefix covering start+n tokens belongs in the table.
            need = self.alloc.blocks_for(start + n)
            tables[i, :need] = self.alloc.page_table(r.rid)[:need]
            slots[i, :n] = self._page_slots(r.rid, np.arange(start, start + n))
            self._length[r.rid] = start + n
            if start + n >= r.prompt_len and r.rid not in self._resumed:
                emit_rows.append((r.rid, i))
        return {"kind": "chunk", "tokens": tokens, "row_pos": row_pos,
                "row_lens": row_lens, "logits_at": logits_at,
                "tables": tables, "slots": slots, "emit_rows": emit_rows,
                "Rb": Rb, "Lb": Lb, "nb": nb}

    def _assemble_prefill(self, entries: List[Tuple[Request, int]],
                          prompts: Dict[int, np.ndarray]) -> List[dict]:
        """Split the decision's prefill rows over the chunk-length and row
        ladders: rows above the top chunk bucket loop over extra dispatches,
        row counts above the top row bucket split across dispatches."""
        asms: List[dict] = []
        work = [[r, self.lengths_of(r), n] for r, n in entries]
        while work:
            step_batch = [(r, s, min(n, CHUNK_BUCKETS[-1])) for r, s, n in work]
            for i in range(0, len(step_batch), ROW_BUCKETS[-1]):
                asm = self._assemble_chunk(
                    step_batch[i:i + ROW_BUCKETS[-1]], prompts)
                asm["group"] = len(asms)
                asms.append(asm)
            nxt = []
            for (r, s, n), (_, _, step) in zip(work, step_batch):
                if n - step > 0:
                    nxt.append([r, s + step, n - step])
            work = nxt
        return asms

    def _assemble_decode(self, reqs: Sequence[Request]) -> dict:
        """Numpy assembly of one fused decode dispatch; the input token ids
        are filled in after the previous round's flush (they are its output)."""
        R = len(reqs)
        Rb = _row_bucket(R)
        new_lens = [self._length[r.rid] + 1 for r in reqs]
        pts = [self.alloc.page_table(r.rid) for r in reqs]
        # decode rows carry their *full* reserved page table (it only changes
        # on grow/evict, never on a per-token page-boundary crossing), so the
        # uploaded table bytes are stable round over round and the device
        # buffer cache actually hits; pages past ceil(len/ps) are never read
        # (the kernel skips them, the oracle masks them).
        nb = _pow2(max(len(pt) for pt in pts))
        tokens = np.zeros((Rb, 1), np.int32)
        lengths = np.zeros((Rb,), np.int32)
        tables = np.zeros((Rb, nb), np.int32)
        slots = np.full((Rb,), self._trash_slot, np.int64)
        for i, (r, pt) in enumerate(zip(reqs, pts)):
            lengths[i] = new_lens[i]
            tables[i, :len(pt)] = pt
            slots[i] = self._page_slots(
                r.rid, np.asarray([new_lens[i] - 1]))[0]
            self._length[r.rid] += 1
        return {"kind": "decode", "rids": [r.rid for r in reqs],
                "tokens": tokens, "lengths": lengths, "tables": tables,
                "slots": slots, "Rb": Rb, "nb": nb}

    def _assemble_spec(self, batch: List[Tuple[Request, np.ndarray]]) -> dict:
        """Numpy assembly of one fused speculative-verify dispatch: each row
        is [pending token, draft_1..draft_k] at the request's resident
        offset, executed through the ragged paged-prefill step. Runs *after*
        the flush (unlike plain decode assembly) because the pending token
        and the write positions depend on the previous round's accepted
        counts. The resident length is advanced optimistically over the
        whole row and rolled back to ``start + emitted`` at the flush."""
        R = len(batch)
        Rb = _row_bucket(R)
        max_n = max(1 + len(d) for _, d in batch)
        # verify rows are narrow (k+1 tokens); the chunk ladder's 16-wide
        # floor would waste 4x the verify compute, so they get their own
        # power-of-two width starting at 2.
        Lb = _pow2(max_n, lo=2)
        pts = [self.alloc.page_table(r.rid) for r, _ in batch]
        # full reserved page table, like decode rows: stable bytes round over
        # round so the device table-upload cache hits.
        nb = _pow2(max(len(pt) for pt in pts))
        tokens = np.zeros((Rb, Lb), np.int32)
        row_pos = np.zeros((Rb,), np.int32)
        row_lens = np.zeros((Rb,), np.int32)
        tables = np.zeros((Rb, nb), np.int32)
        slots = np.full((Rb, Lb), self._trash_slot, np.int64)
        rows: List[Tuple[int, int, int]] = []   # (rid, n_real, start)
        for i, ((r, drafts), pt) in enumerate(zip(batch, pts)):
            rid = r.rid
            start = self._length[rid]
            n = 1 + len(drafts)
            tokens[i, 0] = self._tokens_out[rid][-1]
            tokens[i, 1:n] = drafts
            if n < Lb:
                tokens[i, n:] = tokens[i, n - 1]   # pad; writes hit trash
            row_pos[i] = start
            row_lens[i] = start + n
            tables[i, :len(pt)] = pt
            slots[i, :n] = self._page_slots(rid, np.arange(start, start + n))
            self._length[rid] = start + n
            rows.append((rid, n, start))
        return {"kind": "spec", "tokens": tokens, "row_pos": row_pos,
                "row_lens": row_lens, "tables": tables, "slots": slots,
                "rows": rows, "Rb": Rb, "Lb": Lb, "nb": nb}

    def _dispatch(self, asm: dict):
        """Issue one fused dispatch (async under JAX dispatch); returns the
        device token-id vector — [Rb] for decode/chunk, [Rb*(Lb+1)] payload
        for spec. The RNG nonce advances per dispatch so sampled rounds stay
        reproducible (the sequence of dispatches is deterministic).

        Spans: ``engine.dispatch.<kind>`` around it all, ``engine.upload``
        around the host→device operand uploads, then ``engine.compile`` around
        the jit call the first time a step shape is met (tracing, compiling
        or loading the program) and ``engine.launch`` on every later call."""
        kind = asm["kind"]
        if kind == "decode":
            key = ("decode", asm["Rb"], asm["nb"])
            rows = tokens = int(np.count_nonzero(asm["lengths"]))
        else:
            key = (kind, asm["Rb"], asm["Lb"], asm["nb"])
            n = np.asarray(asm["row_lens"]) - np.asarray(asm["row_pos"])
            rows, tokens = int(np.count_nonzero(n)), int(n.sum())
        with self.spans.span(f"engine.dispatch.{kind}", Rb=asm["Rb"],
                             Lb=asm.get("Lb", 1), nb=asm["nb"], rows=rows,
                             tokens=tokens) as sp:
            with self.spans.span("engine.upload"):
                nonce = self._to_dev(np.int32(self._sample_nonce))
                toks_in = self._to_dev(asm["tokens"])
                tables = self._upload_cached((kind, asm.get("group", 0)),
                                             asm["tables"])
                if kind == "decode":
                    fn = self._jit_decode_fused
                    rest = (self._to_dev(asm["lengths"]), tables,
                            self._to_dev(asm["slots"].astype(np.int32)),
                            nonce)
                else:
                    fn = (self._jit_spec_fused if kind == "spec"
                          else self._jit_chunk_fused)
                    rest = (self._to_dev(asm["row_pos"]),
                            self._to_dev(asm["row_lens"]), tables,
                            self._to_dev(
                                asm["slots"].reshape(-1).astype(np.int32)))
                    rest += ((nonce,) if kind == "spec"
                             else (self._to_dev(asm["logits_at"]), nonce))
            self._sample_nonce += 1
            if self._note_shape(key):
                call = self.spans.span("engine.compile", key=key)
            else:
                call = self.spans.span("engine.launch")
            with call:
                toks, self.cache = fn(self.params, toks_in, self.cache, *rest)
        self.stats.dispatch_s += sp.dur_ns / 1e9
        if kind == "decode":
            self.stats.decode_calls += 1
        elif kind == "spec":
            self.stats.spec_calls += 1
        else:
            self.stats.prefill_calls += 1
        self._round_calls += 1
        return toks

    def _note_shape(self, key) -> bool:
        """Record a step shape; True the first time it is met."""
        if key in self._seen_shapes:
            return False
        self._seen_shapes.add(key)
        self.stats.compiled_shapes += 1
        return True

    # =========================================================================
    # offline compatibility wrapper (shared by both cache modes)
    # =========================================================================
    def serve(self, requests: Sequence[Request],
              prompts: Optional[Dict[int, np.ndarray]] = None,
              max_wall_s: float = 300.0) -> Dict:
        """Serve a complete request list (arrival times are wall-clock
        offsets from this call) and block until everything finishes.

        Thin wrapper over the step API: resets the engine clock, feeds every
        request through ``add_request``, and drives ``step()`` — sleeping
        between arrivals and yielding briefly on empty rounds, exactly as the
        pre-step monolithic loop did. Greedy tokens and the
        one-readback-per-round count are identical to driving ``step()``
        directly. The engine must be drained — resetting the clock under
        live requests would corrupt their arrival-relative deadlines."""
        assert not self.has_work(), \
            "serve() on an engine with live requests (drain or use step())"
        rng = np.random.default_rng(0)
        prompts = prompts or {
            r.rid: rng.integers(0, self.cfg.vocab_size, r.prompt_len).astype(np.int32)
            for r in requests
        }
        self._t0 = time.perf_counter()
        done0 = len(self._done)
        for r in sorted(requests, key=lambda r: r.arrival):
            self.add_request(r, prompts[r.rid])

        empty_rounds = 0
        while self.has_work() and self._now() < max_wall_s:
            self.step()
            if self._progress == "executed":
                empty_rounds = 0
            elif self._progress == "empty":
                # every entry was evicted away (severe KV pressure): yield so
                # re-admission can make progress — but if no eviction changed
                # any state either, the engine is wedged (e.g. a lone request
                # outgrew total capacity); bail instead of spinning to the
                # wall clock.
                empty_rounds += 1
                if self._last_round_evictions == 0 and empty_rounds >= 8:
                    break
                time.sleep(1e-3)
            elif self._progress == "no-decision":
                time.sleep(1e-3)
            else:   # idle: nothing admitted/admissible (in-flight is flushed)
                if self._pending:
                    time.sleep(max(self._pending[0][0] - self._now(), 0.0)
                               + 1e-4)
                elif self._queued:   # arrived but nothing fits: wedged
                    break

        # the final flush's events have no step() caller to collect them;
        # drop them so a later driver of this engine doesn't receive stale
        # TOKEN/FINISHED events for long-gone requests.
        self.flush()
        wall = self._now()
        return {
            "finished": self._done[done0:],
            "unfinished": [r for r in requests if r.state != ReqState.FINISHED],
            "stats": self.stats,
            "outputs": dict(self._tokens_out),
            "wall": wall,
        }

    # ---- per-mode decision execution -----------------------------------------
    def _execute_slot(self, decision) -> List[Tuple[Request, int, int]]:
        executed = []
        decode_reqs = [r for r, n in decision.alloc
                       if r.state == ReqState.DECODING]
        if decode_reqs:
            self._run_decode_slot(decode_reqs)
            executed += [(r, 1, r.context_len()) for r in decode_reqs]
        for r, n in decision.alloc:
            if r.state != ReqState.DECODING:
                ctx = r.context_len()
                n_exec = self._run_prefill_chunk(r, n, self._prompts[r.rid])
                if n_exec > 0:
                    executed.append((r, n_exec, ctx))
        return executed

    def _execute_paged(self, decision) -> List[Tuple]:
        """Grow allocations (evicting under pressure), assemble the round on
        the host while the previous round still runs on device, sync once on
        the previous round's token ids, then dispatch the decision as fused
        decode + speculative-verify + ragged prefill batches (all async).

        With ``spec_k > 0`` the round's one readback moves *before* assembly
        instead of after: round N's accepted counts decide round N+1's write
        positions (host-side rollback) and drafting needs the newest emitted
        token host-visible. Still exactly one readback per round — only the
        host assembly loses its overlap with the device. At ``spec_k == 0``
        the original assemble-then-flush order is untouched."""
        prompts = self._prompts
        protected = {r.rid for r, _ in decision.alloc}
        ev0 = self.alloc.evictions
        spec_on = self.spec_k > 0 and self.drafter is not None
        if spec_on:
            self._flush_round()
        self._round_spec_rids = set()

        def is_live(r):  # an earlier grow may have evicted a later entry
            return r.rid in self.alloc.owners and r.state not in (
                ReqState.FINISHED, ReqState.ABORTED)

        n_ev = len(self._events)
        with self.spans.span("engine.grow") as sp:
            pressure = self._spec_pressure() if spec_on else False
            decode_rows: List[Request] = []
            spec_rows: List[Tuple[Request, np.ndarray]] = []
            prefill_rows: List[Tuple[Request, int]] = []
            for r, n in decision.alloc:
                if not is_live(r):
                    continue
                if r.state == ReqState.DECODING:
                    drafts = (self._propose_drafts(r, pressure) if spec_on
                              else None)
                    if drafts is not None and self._grow_or_evict(
                            r, self._length[r.rid] + 1 + len(drafts),
                            protected):
                        spec_rows.append((r, drafts))
                    elif self._grow_or_evict(r, self._length[r.rid] + 1,
                                             protected):
                        decode_rows.append(r)
                else:
                    n_exec = min(n, r.remaining_prefill())
                    if n_exec <= 0:
                        continue
                    start = self._length.get(r.rid, 0)
                    # admission reserved the full remaining prompt, so this
                    # grow is a no-op today; it stays so a future
                    # partial-reservation admission policy still allocates
                    # (or skips) correctly.
                    if not self._grow_or_evict(r, start + n_exec, protected):
                        continue
                    prefill_rows.append((r, n_exec))
            decode_rows = [r for r in decode_rows if is_live(r)]
            spec_rows = [(r, d) for r, d in spec_rows if is_live(r)]
            prefill_rows = [(r, n) for r, n in prefill_rows if is_live(r)]
            sp.args["evicted"] = [e.rid for e in self._events[n_ev:]
                                  if e.kind == EventKind.EVICTED]
        self._last_round_evictions = self.alloc.evictions - ev0
        if not decode_rows and not spec_rows and not prefill_rows:
            return []

        # ---- host-side numpy assembly (device still busy with round N) ------
        with self.spans.span("engine.assemble"):
            executed: List[Tuple] = []
            decode_asms: List[dict] = []
            if decode_rows:
                ctxs = {r.rid: r.context_len() for r in decode_rows}
                for i in range(0, len(decode_rows), ROW_BUCKETS[-1]):
                    asm = self._assemble_decode(
                        decode_rows[i:i + ROW_BUCKETS[-1]])
                    asm["group"] = i // ROW_BUCKETS[-1]
                    decode_asms.append(asm)
                executed += [(r, 1, ctxs[r.rid]) for r in decode_rows]
            spec_asms: List[dict] = []
            if spec_rows:
                self.stats.spec_rounds += 1
                for i in range(0, len(spec_rows), ROW_BUCKETS[-1]):
                    asm = self._assemble_spec(
                        spec_rows[i:i + ROW_BUCKETS[-1]])
                    asm["group"] = i // ROW_BUCKETS[-1]
                    spec_asms.append(asm)
                for r, drafts in spec_rows:
                    self._round_spec_rids.add(r.rid)
                    executed.append((r, 1 + len(drafts), r.context_len(),
                                     len(drafts)))
            if spec_on:
                self._feed_spec_signals(
                    sum(len(d) for _, d in spec_rows),
                    len(decode_rows) + len(spec_rows))
            chunk_asms: List[dict] = []
            if prefill_rows:
                ctxs = {r.rid: r.context_len() for r, _ in prefill_rows}
                chunk_asms = self._assemble_prefill(prefill_rows, prompts)
                executed += [(r, n, ctxs[r.rid]) for r, n in prefill_rows]
                for r, n in prefill_rows:
                    self.stats.prefill_tokens += n
                    self._commit(r.rid)   # freeze pages this round fills

        # ---- the round's single sync: round N's token ids -------------------
        # (no-op when spec_on already flushed above — never a second sync)
        self._flush_round()

        # ---- dispatch round N+1 (async) -------------------------------------
        t_disp = time.perf_counter()
        toks, emits, spec_emits, off = [], [], [], 0
        for asm in decode_asms:
            # decode inputs are round N's outputs — only now host-visible
            for i, rid in enumerate(asm["rids"]):
                r = self._reqs.get(rid)
                if r is None or r.state in (ReqState.FINISHED,
                                            ReqState.ABORTED):
                    # the flush above finished this request (stop token): its
                    # row was assembled before the ids were host-visible —
                    # execute it dead (KV write to the trash page, no emit).
                    asm["slots"][i] = self._trash_slot
                    continue
                prev = self._tokens_out.get(rid)
                asm["tokens"][i, 0] = prev[-1] if prev else 0
                emits.append((rid, off + i))
                # the write slot this row fills (position _length-1) may have
                # completed a page; its content — prompt + emitted ids — is
                # host-known now, so decode pages freeze too (multi-turn
                # follow-ups match across generated output).
                self._commit(rid)
            toks.append(self._dispatch(asm))
            off += asm["Rb"]
        for asm in spec_asms:
            toks.append(self._dispatch(asm))
            W = asm["Lb"] + 1
            for i, (rid, n_real, start) in enumerate(asm["rows"]):
                spec_emits.append((rid, off + i * W, asm["Lb"], n_real,
                                   start))
                # only the pending token's position (start) is confirmed
                # content — draft positions must not freeze until accepted.
                self._commit(rid, upto=start + 1)
            off += asm["Rb"] * W
        for asm in chunk_asms:
            toks.append(self._dispatch(asm))
            emits += [(rid, off + row) for rid, row in asm["emit_rows"]
                      if rid in self._reqs]
            off += asm["Rb"]
        self._inflight = _InflightRound(toks=toks, emits=emits,
                                        t_dispatch=t_disp,
                                        spec_emits=spec_emits)
        return executed


# Back-compat name: the engine core was born as the monolithic ServingEngine;
# existing callers (tests, benchmarks) keep the old import path.
ServingEngine = EngineCore
