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
