"""Trace reduction: a small trace recorded on the CPU, and the attribution
of a kernel to the program execution that encloses it."""
from __future__ import annotations

from slobench import tracefile
from slobench.tracefile import Event, Trace


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    def decode_fused(x, w):
        return jnp.tanh(x @ w) @ w

    def chunk_fused(x, w):
        return jnp.tanh(jnp.tanh(x @ w) @ w) @ w

    dec, chk = jax.jit(decode_fused), jax.jit(chunk_fused)
    x, w = jnp.ones((128, 128)), jnp.full((128, 128), 0.01)
    jax.block_until_ready((dec(x, w), chk(x, w)))
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("slobench.dispatch.decode"):
            jax.block_until_ready(dec(x, w))
        with jax.profiler.TraceAnnotation("slobench.dispatch.chunk"):
            jax.block_until_ready(chk(x, w))
    jax.profiler.stop_trace()
    tr = tracefile.load(tracefile.find(str(tmp_path)))
    progs = {o.program for o in tr.ops}
    assert {"jit_decode_fused", "jit_chunk_fused"} <= progs
    assert any(h.name == "slobench.dispatch.chunk" for h in tr.host)
    busy = tracefile.busy_s(tr)
    span = (max(o.end for o in tr.ops) - min(o.start for o in tr.ops)) / 1e9
    assert 0 < busy <= span
    top = tracefile.top_ops(tr, 3)
    assert len(top) == 3 and top[0][1] >= top[-1][1] > 0


def synthetic():
    dev = "/device:TPU:0"
    programs = [Event("jit_decode_fused", 0, 100, dev),
                Event("jit_chunk_fused", 150, 100, dev)]
    ops = [Event("_fused_kernel", 10, 30, dev), Event("fusion.1", 50, 40, dev),
           Event("_fused_kernel", 160, 60, dev), Event("copy", 230, 10, dev)]
    tracefile.attribute(ops, programs)
    return Trace(ops, programs, [Event("slobench.schedule", 90, 70)], [dev])


def test_kernel_attributed_by_enclosing_program():
    tr = synthetic()
    dec = tracefile.program_ops(tr, "jit_decode_fused", "fused_kernel")
    chk = tracefile.program_ops(tr, "jit_chunk_fused", "fused_kernel")
    assert [o.dur for o in dec] == [30] and [o.dur for o in chk] == [60]
    assert len(tracefile.executions(tr, "jit_chunk_fused")) == 1


def test_busy_union_and_idle_by_host():
    tr = synthetic()
    assert tracefile.busy_intervals(tr.ops, tr.devices[0]) == [
        (10, 40), (50, 90), (160, 220), (230, 240)]
    assert abs(tracefile.busy_s(tr) - 140e-9) < 1e-15
    idle = dict(tracefile.idle_by_host(tr, 0, 250))
    # idle: [0,10) [40,50) [90,160) [220,230) [240,250) = 110 ns; the
    # schedule span [90,160) covers 70 of it
    assert abs(idle["slobench.schedule"] - 70e-9) < 1e-15
    assert abs(idle["other"] - 40e-9) < 1e-15
    assert tracefile.program_name("jit_decode_fused(17)") == "jit_decode_fused"


def test_op_names_from_tpu_hlo_text():
    kern = ('%closed_call.11 = bf16[16,8,2,128]{3,2,1,0:T(2,128)(2,1)S(1)} '
            'custom-call(s32[16,256]{1,0:T(8,128)S(1)} %copy-done.9), '
            'custom_call_target="tpu_custom_call"')
    loop = ('%while.4 = (s32[]{:T(128)}, bf16[32,1,2048]{2,0,1:T(8,128)(2,1)'
            'S(1)}, /*index=5*/bf16[28,128]{1,0:T(8,128)(2,1)}) while(%t)')
    fus = ('%copy_dynamic-update-slice_fusion.2 = bf16[28,8]{1,0:T(8,128)} '
           'fusion(bf16[28,8]{1,0} %get-tuple-element.571), kind=kLoop')
    assert tracefile.op_name(kern) == (
        "%closed_call.11 custom-call tpu_custom_call", "custom-call")
    assert tracefile.op_name(loop)[1] == "while"
    assert tracefile.op_name(fus) == (
        "%copy_dynamic-update-slice_fusion.2 fusion", "fusion")


CONF = {"family": "qwen3_dense", "num_hidden_layers": 2, "hidden_size": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "intermediate_size": 16, "vocab_size": 32}
PEAK = {"bf16_flops": 2.0 ** 20, "hbm_bytes_s": 2.0 ** 10}  # exact quotients
DECODE_KERNEL = r"paged_decode_attention.*tpu_custom_call"
S = 10 ** 9                                                  # 1 s in ns


def decode_run(second_kernel: bool):
    """Two traced executions of ``jit_decode_fused``, each with the decode
    attention kernel once per layer (375 ms each), a fusion, and with
    ``second_kernel`` another Pallas kernel of 1 s; the host dispatched
    both steps over rows of 3 and 5 tokens, and a chunk beside them."""
    import types

    import numpy as np
    dev = "/device:TPU:0"
    programs, ops = [], []
    for i in range(2):
        t0 = 10 * i * S
        programs.append(Event("jit_decode_fused", t0, 5 * S, dev))
        for layer in range(2):
            ops.append(Event("%paged_decode_attention.8 custom-call "
                             "tpu_custom_call", t0 + layer * S, 375 * 10 ** 6,
                             dev))
        ops.append(Event("%fusion.3 fusion", t0 + 2 * S, S, dev))
        if second_kernel:
            ops.append(Event("%expert_ffn.2 custom-call tpu_custom_call",
                             t0 + 3 * S, S, dev))
    tracefile.attribute(ops, programs)
    meta = np.array([3, 5, 0])
    dispatches = [(1.0, "decode", 4, 1, 2, meta), (1.5, "chunk", 1, 16, 1,
                                                   np.array([[0], [4]])),
                  (2.0, "decode", 4, 1, 2, meta)]
    return types.SimpleNamespace(
        trace=Trace(ops, programs, [], [dev]), peak=PEAK, conf=CONF,
        probe=types.SimpleNamespace(dispatches=dispatches),
        trace_t0=0.5, trace_t1=3.0)


def test_attention_roofline_reads_its_kernel_by_name():
    from slobench import rooflines

    # per call and layer: 256 flops, 192 bytes -> 0.1875 s, memory bound;
    # 2 calls x 2 layers = 0.75 s of least time over 4 x 375 ms of kernel,
    # as the per-layer count times the layers read before
    for second in (False, True):
        run = decode_run(second)
        assert rooflines.share(run, "jit_decode_fused", "decode",
                               DECODE_KERNEL) == 50.0
    # any Pallas kernel would have added the second kernel's 2 s
    assert rooflines.share(run, "jit_decode_fused", "decode",
                           "tpu_custom_call") == 100.0 * 0.75 / 3.5


def test_attention_roofline_prices_the_whole_step_by_the_family():
    from unittest import mock

    from slobench import rooflines, spec

    # a family whose layers are not all alike gives the whole step's work:
    # it is not multiplied by the number of layers again
    with mock.patch.object(spec.family(CONF), "attention",
                           lambda conf, kind, meta: (0, 768)):
        assert rooflines.share(decode_run(True), "jit_decode_fused",
                               "decode", DECODE_KERNEL) == 100.0


def idle_by_host_reference(tr, t0, t1, k=10):
    """The plain loop: every span before the gap's end, for every gap."""
    from collections import defaultdict
    busy = tracefile.busy_intervals(tr.ops, tr.devices[0])
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    acc = defaultdict(int)
    for gs, ge in gaps:
        covered = 0
        for h in sorted(tr.host, key=lambda h: (h.start, -h.dur)):
            if h.start >= ge:
                break
            ov = min(h.end, ge) - max(h.start, gs)
            if ov > 0:
                acc[h.name] += ov
                covered += ov
        if ge - gs > covered:
            acc["other"] += ge - gs - covered
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v / 1e9] for n, v in top]


def test_idle_by_host_equals_the_plain_loop():
    import random
    rng = random.Random(5)
    dev = "/device:TPU:0"
    ops, t = [], 0
    for _ in range(3000):
        t += rng.randint(1, 400)
        ops.append(Event("op", t, rng.randint(1, 300), dev))
    host = [Event(f"slobench.{rng.choice('abcde')}", rng.randint(0, t),
                  rng.choice([rng.randint(1, 50), rng.randint(1, 5000)]))
            for _ in range(400)]
    tr = Trace(ops, [], host, [dev])
    for t0, t1 in ((0, t + 500), (1000, t // 2)):
        assert tracefile.idle_by_host(tr, t0, t1) == \
            idle_by_host_reference(tr, t0, t1)
