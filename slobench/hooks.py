"""Every reach of the benchmark into the program's private names.

The serving engine has no public hook for these yet (``PERF.md`` lists each
as a program change for a later PR). Keeping them in this one file means a
rename in the engine breaks this file, loudly, and nothing else:

* :func:`build_server` hands the benchmark's weights to ``EngineCore`` in
  place of its eager ``repro.serving.engine.init_params``;
* :func:`warm` issues one dispatch per listed step shape through
  ``EngineCore._dispatch``, the engine's own calling convention;
* :class:`Probe` wraps the scheduler's ``schedule`` and the engine's
  ``_dispatch`` and ``_flush_round``;
* :func:`seen_shapes`, :func:`backlog`, :func:`flush` and :func:`free` read,
  drain or drop engine state.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List
from unittest import mock

import jax
import numpy as np


def build_server(conf: dict, cfg, params):
    """The engine as the serving entry (``launch/serve.py``) builds it for the
    configuration file ``conf`` (``cfg`` its ``ModelConfig``), loaded with
    ``params``, behind ``InferenceServer``."""
    from repro.core import SlidingServeScheduler
    from repro.serving import engine as engine_mod
    from repro.serving.server import InferenceServer
    eng = conf["engine"]
    sched = SlidingServeScheduler(max_budget=eng["max_budget"],
                                  max_iter_time=eng["max_iter_time"])
    with mock.patch.object(engine_mod, "init_params", lambda *_: params):
        core = engine_mod.EngineCore(
            cfg, sched, cache_mode="paged",
            kv_capacity_tokens=conf["kv_pool_tokens"],
            page_size=eng["page_size"],
            decode_reserve_tokens=eng["decode_reserve_tokens"],
            overlap=eng["overlap"], prefix_cache=eng["prefix_cache"],
            spec_k=eng["spec_k"])
    return InferenceServer(core)


def warm(core, shapes: dict) -> int:
    """Dispatch the engine's decode and chunk steps once at every listed
    shape (one real token per row, written to the trash page), and the
    readback's concatenation of their outputs, so that the window compiles
    nothing; returns how many step shapes were run."""
    trash, n = core._trash_slot, 0
    outs = {"decode": [], "chunk": []}
    for rb, nb in shapes.get("decode", []):
        ones = np.ones((rb,), np.int32)
        outs["decode"].append(core._dispatch({
            "kind": "decode", "Rb": rb, "nb": nb,
            "tokens": np.zeros((rb, 1), np.int32), "lengths": ones,
            "tables": np.zeros((rb, nb), np.int32),
            "slots": np.full((rb,), trash, np.int64)}))
        n += 1
    for rb, lb, nb in shapes.get("chunk", []):
        zeros = np.zeros((rb,), np.int32)
        outs["chunk"].append(core._dispatch({
            "kind": "chunk", "Rb": rb, "Lb": lb, "nb": nb,
            "tokens": np.zeros((rb, lb), np.int32), "row_pos": zeros,
            "row_lens": zeros + 1, "logits_at": zeros,
            "tables": np.zeros((rb, nb), np.int32),
            "slots": np.full((rb, lb), trash, np.int64)}))
        n += 1
    # a round's token ids are read back as one vector: decode rows, then
    # chunk rows (EngineCore._flush_round)
    for a in {x.shape: x for x in outs["decode"]}.values():
        for b in {x.shape: x for x in outs["chunk"]}.values():
            jax.numpy.concatenate([a, b])
    jax.block_until_ready(core.cache)
    return n


def seen_shapes(core) -> set:
    """Every step shape the engine has dispatched, as its ``_note_shape``
    keys: ("decode", Rb, nb) and ("chunk", Rb, Lb, nb)."""
    return set(core._seen_shapes)


def backlog(core) -> int:
    """Requests queued or admitted and not finished."""
    return len(core._queued) + len(core._active)


def flush(server) -> None:
    """Materialise the engine's round in flight and route its events."""
    server._route(server.core.flush())


def free(core) -> None:
    """Drop the engine's device state: the KV pool and cached uploads."""
    core.cache = None
    core._dev_cache.clear()


@dataclasses.dataclass
class Round:
    t: float                    # host clock at schedule() entry
    sched_s: float              # host seconds inside schedule()


class Probe:
    """Benchmark-side spans and counters around the engine's layers, on the
    host's clock. With ``annotate`` each wrapped call is also a span in the
    profiler's trace (``jax.profiler.TraceAnnotation``), so that idle gaps on
    the device can be put beside what the host was doing."""

    def __init__(self, server, annotate: bool = False):
        core = server.core
        self.core = core
        self.annotate = annotate
        self.rounds: List[Round] = []
        self.dispatches: List[tuple] = []  # (t, kind, Rb, Lb, nb, meta)
        self.live_pages: List[tuple] = []  # (t, live pages) after each step
        self._schedule = core.sched.schedule
        self._dispatch, self._flush = core._dispatch, core._flush_round
        core.sched.schedule = self.schedule
        core._dispatch = self.dispatch
        core._flush_round = self.flush

    def _span(self, name):
        if self.annotate:
            return jax.profiler.TraceAnnotation(name)
        return _NULL

    def schedule(self, *args, **kw):
        t0 = time.perf_counter()
        with self._span("slobench.schedule"):
            d = self._schedule(*args, **kw)
        self.rounds.append(Round(t0, time.perf_counter() - t0))
        return d

    def dispatch(self, asm):
        if asm["kind"] == "decode":
            meta = np.asarray(asm["lengths"], np.int64).copy()
        else:
            meta = np.stack([np.asarray(asm["row_pos"], np.int64),
                             np.asarray(asm["row_lens"], np.int64)])
        self.dispatches.append((time.perf_counter(), asm["kind"], asm["Rb"],
                                asm.get("Lb", 1), asm["nb"], meta))
        with self._span(f"slobench.dispatch.{asm['kind']}"):
            return self._dispatch(asm)

    def flush(self):
        with self._span("slobench.readback"):
            return self._flush()

    def after_step(self) -> None:
        """Called after every ``server.step()``."""
        self.live_pages.append((time.perf_counter(),
                                self.core.alloc.live_blocks))


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()
