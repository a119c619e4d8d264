"""Paged-attention decode Pallas TPU kernels.

One new query token per sequence attends over a *paged* KV cache: physical
pages of ``page_size`` tokens indexed through a per-sequence block table
(vLLM's PagedAttention layout, §4 substrate).

Two generations live here:

* ``paged_attention`` — the original split-layout kernel (separate K and V
  pools, grid ``(B, Hkv, pages_per_seq)``, page DMA left to the implicit
  Pallas grid pipeline). Kept as the layout/DMA A/B baseline for
  ``bench_microkernels``.
* ``paged_attention_fused`` — the production kernel over the fused
  head-interleaved pool ``[Hkv, P, 2, page_size, D]`` (K at interleave 0,
  V at 1). The pool stays in HBM (``ANY`` memory space) and the kernel
  **double-buffers page DMA explicitly**: grid ``(B, Hkv)`` with the page
  axis as an in-kernel loop, ping-pong VMEM scratch ``[2, 2, ps, D]`` and a
  2-deep DMA semaphore array, so the HBM→VMEM copy of page ``i+1`` overlaps
  the flash-attention compute of page ``i`` — and one DMA moves K *and* V
  for a page (half the DMA count of the split layout).
  ``partial=True`` emits the un-normalized flash state ``(acc, m, l)``
  instead of dividing — the sequence-sharded mesh fallback combines those
  across shards (``pmax``/``psum``); finalizing the partials reproduces the
  full kernel's output bit-exactly (same loop, same final division).

Common TPU adaptations (vs. the CUDA kernel):

* block table + lengths are **scalar-prefetch** operands, so (sequence,
  logical page) -> physical page translation happens on the scalar core (no
  pointer chasing on the compute path, no per-warp gather).
* per-step compute is a [G, D] x [D, page_size] MXU matmul per kv head —
  decode is HBM-bound, and both kernels stream each KV page exactly once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(block_tables_ref, lengths_ref,   # scalar prefetch
            q_ref, k_ref, v_ref,             # [1,1,G,D], [1,ps,D], [1,ps,D]
            o_ref,                           # [1,1,G,D]
            m_ref, l_ref, acc_ref,           # VMEM scratch [G],[G],[G,D]
            *, scale: float, window: int, softcap: float,
            page_size: int, num_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    pages_needed = (length + page_size - 1) // page_size

    @pl.when(j < pages_needed)
    def _compute():
        q = q_ref[0, 0]                                  # [G, D]
        k = k_ref[0, 0]                                  # [ps, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, ps]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = k_pos < length
        if window > 0:
            mask &= k_pos >= length - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == num_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_attention(
    q: jnp.ndarray,             # [B, H, D]
    k_pages: jnp.ndarray,       # [Hkv, P_total, page_size, D]
    v_pages: jnp.ndarray,       # [Hkv, P_total, page_size, D]
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    lengths: jnp.ndarray,       # [B] int32
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, D = q.shape
    Hkv, P_total, page_size, _ = k_pages.shape
    G = H // Hkv
    pages_per_seq = block_tables.shape[1]

    qg = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        page_size=page_size, num_pages=pages_per_seq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, j, bt, L: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda b, h, j, bt, L: (h, bt[b, j], 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda b, h, j, bt, L: (h, bt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, j, bt, L: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, k_pages, v_pages)
    return out.reshape(B, H, D)


# =============================================================================
# fused head-interleaved layout + explicit double-buffered page DMA
# =============================================================================
K_IDX, V_IDX = 0, 1   # interleave positions inside a fused page


def _fused_kernel(bt_ref, len_ref,   # scalar prefetch: [B, n], [B]
                  q_ref, kv_hbm,     # [1,1,G,D] VMEM, [Hkv,P,2,ps,D] HBM
                  *refs,             # outputs, then (scratch, sem)
                  scale: float, window: int, softcap: float,
                  page_size: int, num_pages: int, partial: bool,
                  dma_depth: int):
    if partial:
        o_ref, m_out, l_out = refs[0], refs[1], refs[2]
        scratch, sem = refs[3], refs[4]
    else:
        o_ref, m_out, l_out = refs[0], None, None
        scratch, sem = refs[1], refs[2]
    b = pl.program_id(0)
    h = pl.program_id(1)
    G, D = q_ref.shape[2], q_ref.shape[3]

    length = len_ref[b]
    pages_needed = jnp.minimum(
        (length + page_size - 1) // page_size, num_pages)

    def dma(slot, j):
        # one async copy moves the page's K and V planes together (the
        # fused-layout win: half the DMA issue rate of split pools).
        return pltpu.make_async_copy(
            kv_hbm.at[h, bt_ref[b, j]], scratch.at[slot], sem.at[slot])

    # warmup: fill the ring — up to depth-1 copies in flight before the
    # loop's first wait (depth 2 reduces to the classic single ping).
    for i in range(dma_depth - 1):
        @pl.when(i < pages_needed)
        def _warmup(i=i):
            dma(i, i).start()

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        slot = jax.lax.rem(j, dma_depth)
        # overlap: kick off page j+depth-1's HBM->VMEM copy into the slot
        # freed at iteration j-1, keeping depth-1 copies in flight while
        # page j computes.
        nxt = j + dma_depth - 1
        @pl.when(nxt < pages_needed)
        def _prefetch_next():
            dma(jax.lax.rem(nxt, dma_depth), nxt).start()
        dma(slot, j).wait()
        k = scratch[slot, K_IDX]                         # [ps, D]
        v = scratch[slot, V_IDX]
        q = q_ref[0, 0]                                  # [G, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, ps]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = k_pos < length
        if window > 0:
            mask &= k_pos >= length - window
        s = jnp.where(mask, s, NEG_INF)
        # row statistics ride as [G, 1] columns so the partial outputs can
        # keep a trailing singleton (a legal TPU block) without a relayout.
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc_prev * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, pages_needed, body,
        (jnp.full((G, 1), NEG_INF, jnp.float32),
         jnp.zeros((G, 1), jnp.float32), jnp.zeros((G, D), jnp.float32)))
    if partial:
        o_ref[0, 0] = acc
        m_out[0, 0] = m
        l_out[0, 0] = l
    else:
        o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention_fused(
    q: jnp.ndarray,             # [B, H, D]
    kv_pages: jnp.ndarray,      # [Hkv, P_total, 2, page_size, D]
    block_tables: jnp.ndarray,  # [B, pages_per_seq] int32
    lengths: jnp.ndarray,       # [B] int32 (may be shard-local, see below)
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    partial: bool = False,
    dma_depth: int = 2,
    interpret: bool = False,
):
    """Fused-layout decode attention with ring-buffered page DMA.

    ``dma_depth`` sets the VMEM ring depth: depth N keeps up to N-1 page
    copies in flight behind the one being computed (2 = the classic
    ping-pong double buffer; deeper rings absorb burstier HBM latency at
    ``(N-2) * 2 * page_size * D`` extra VMEM per grid cell). Output is
    bit-identical across depths — only the copy schedule changes.

    ``partial=False`` returns ``[B, H, D]`` in q's dtype. ``partial=True``
    returns the un-normalized flash state ``(acc [B,H,D] f32, m [B,H] f32,
    l [B,H] f32)`` for the cross-shard flash-decode combine; ``lengths``
    may then be shard-local (global length minus the shard's key offset) —
    both masks depend only on ``length - k_pos``. Finalizing the partials
    (``acc / max(l, 1e-30)``) matches the ``partial=False`` output
    bit-exactly: same loop, same division.
    """
    B, H, D = q.shape
    Hkv, P_total, two, page_size, _ = kv_pages.shape
    assert two == 2, kv_pages.shape
    assert dma_depth >= 2, dma_depth
    G = H // Hkv
    pages_per_seq = block_tables.shape[1]

    qg = q.reshape(B, Hkv, G, D)

    kernel = functools.partial(
        _fused_kernel, scale=scale, window=window, softcap=softcap,
        page_size=page_size, num_pages=pages_per_seq, partial=partial,
        dma_depth=dma_depth)

    if partial:
        # m/l carry a trailing singleton: a (1, 1, G) block over (B, Hkv, G)
        # is refused by the TPU compiler (the last two block dims must tile
        # by (8, 128) or equal the array's), while (G, 1) equals it.
        stat = pl.BlockSpec((1, 1, G, 1), lambda b, h, bt, L: (b, h, 0, 0))
        out_shape = (jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
                     jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32),
                     jax.ShapeDtypeStruct((B, Hkv, G, 1), jnp.float32))
        out_specs = (
            pl.BlockSpec((1, 1, G, D), lambda b, h, bt, L: (b, h, 0, 0)),
            stat, stat)
    else:
        out_shape = jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype)
        out_specs = pl.BlockSpec((1, 1, G, D),
                                 lambda b, h, bt, L: (b, h, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, bt, L: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((dma_depth, 2, page_size, D), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((dma_depth,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      qg, kv_pages)
    if partial:
        acc, m, l = out
        return acc.reshape(B, H, D), m.reshape(B, H), l.reshape(B, H)
    return out.reshape(B, H, D)
