"""Paged-prefill (ragged chunked-prefill) attention Pallas TPU kernels.

Each batch row is a *chunk* of a different request's prompt, sitting at its
own cache offset ``row_pos[r]``, attending over that request's paged KV
(physical pages of ``page_size`` tokens indexed through a per-row block
table). This is the fused ragged mixed-batch shape the serving engine's
scheduler emits; computing it directly over the block tables removes the
dense ``gather_pages`` materialization (O(R*S*H*D) HBM traffic per layer)
and the [R, H, G, Sq, Sk] score tensor of the jnp path.

Two generations live here (mirroring ``paged_attention/kernel.py``):

* ``paged_prefill_attention`` — the original split-layout kernel (separate
  K/V pools, page axis in the grid, DMA left to the implicit Pallas grid
  pipeline). Kept as the layout/DMA A/B baseline for ``bench_microkernels``.
* ``paged_prefill_attention_fused`` — the production kernel over the fused
  head-interleaved pool ``[Hkv, P, 2, page_size, D]``: the pool stays in HBM
  (``ANY`` memory space), the page axis is an in-kernel loop bounded by the
  causal/window/length page range (pruned pages cost neither FLOPs *nor*
  DMA), and page copies ping-pong through a 2-deep VMEM scratch so the
  HBM→VMEM copy of page ``i+1`` overlaps the compute of page ``i`` — one
  DMA moving K and V together. ``partial=True`` emits the un-normalized
  flash state ``(acc, m, l)`` for the sequence-sharded mesh fallback;
  finalizing it reproduces ``partial=False`` bit-exactly.

TPU adaptation (vs. the CUDA chunked-prefill kernels vLLM drives):

* the block table, row offsets and row lengths are **scalar-prefetch**
  operands — the K/V BlockSpec index maps translate (row, logical page) ->
  physical page, so page gathers become ordinary prefetched VMEM tile loads
  (no pointer chasing on the compute path).
* grid ``(R, Hkv, num_q_tiles, num_pages)``; the page axis is innermost and
  sequential, so the online-softmax state (m, l, acc) for a q tile rides in
  VMEM scratch across pages; pages past ``ceil(len/page_size)`` or entirely
  above the causal diagonal / below the sliding window skip their FLOPs with
  ``pl.when``.
* GQA without KV repetition: q is laid out ``[R, Hkv, Sq*G, D]`` (grouped
  query heads interleaved per token), so each page is one
  [bq*G, D] x [D, page_size] MXU matmul per kv head and every KV page is
  streamed exactly once per (row, kv head).
* fp32 softmax state; matmuls accumulate fp32 via ``preferred_element_type``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, pos_ref, len_ref,      # scalar prefetch: [R,n],[R],[R]
            q_ref, k_ref, v_ref,           # [1,1,bq*G,D], [1,1,ps,D], [1,1,ps,D]
            o_ref,                         # [1,1,bq*G,D]
            m_ref, l_ref, acc_ref,         # VMEM scratch [bq*G],[bq*G],[bq*G,D]
            *, scale: float, window: int, softcap: float,
            page_size: int, num_pages: int, block_q: int, group: int):
    r = pl.program_id(0)
    qi = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[r]
    pos = pos_ref[r]
    pages_needed = (length + page_size - 1) // page_size
    # causal pruning: q tile qi covers absolute positions
    # [pos + qi*bq, pos + (qi+1)*bq); page j covers keys [j*ps, (j+1)*ps).
    live = (j < pages_needed) & (j * page_size <= pos + (qi + 1) * block_q - 1)
    if window > 0:
        # window pruning: the lowest key any q row of this tile can see
        live &= (j + 1) * page_size - 1 >= pos + qi * block_q - window + 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0]                                  # [bq*G, D]
        k = k_ref[0, 0]                                  # [ps, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq*G, ps]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        q_pos = pos + qi * block_q + t
        k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (k_pos <= q_pos) & (k_pos < length)
        if window > 0:
            mask &= q_pos - k_pos < window
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


def paged_prefill_attention(
    q: jnp.ndarray,             # [R, Sq, Hkv, G, D] chunk queries
    k_pages: jnp.ndarray,       # [Hkv, P_total, page_size, D]
    v_pages: jnp.ndarray,       # [Hkv, P_total, page_size, D]
    block_tables: jnp.ndarray,  # [R, num_pages] int32
    row_pos: jnp.ndarray,       # [R] int32 cache offset per row
    lengths: jnp.ndarray,       # [R] int32 post-chunk valid length per row
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [R, Sq, Hkv, G, D] (same contract as the jnp oracle)."""
    R, Sq, Hkv, G, D = q.shape
    _, _, page_size, _ = k_pages.shape
    num_pages = block_tables.shape[1]
    block_q = min(block_q, Sq)
    assert Sq % block_q == 0, (Sq, block_q)
    nq = Sq // block_q

    # [R, Hkv, Sq*G, D]: token t's G grouped heads are rows [t*G, (t+1)*G)
    qf = q.transpose(0, 2, 1, 3, 4).reshape(R, Hkv, Sq * G, D)

    kernel = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap,
        page_size=page_size, num_pages=num_pages, block_q=block_q, group=G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, Hkv, nq, num_pages),
        in_specs=[
            pl.BlockSpec((1, 1, block_q * G, D),
                         lambda r, h, i, j, bt, pos, L: (r, h, i, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda r, h, i, j, bt, pos, L: (h, bt[r, j], 0, 0)),
            pl.BlockSpec((1, 1, page_size, D),
                         lambda r, h, i, j, bt, pos, L: (h, bt[r, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q * G, D),
                               lambda r, h, i, j, bt, pos, L: (r, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q * G,), jnp.float32),
            pltpu.VMEM((block_q * G,), jnp.float32),
            pltpu.VMEM((block_q * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, Hkv, Sq * G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), row_pos.astype(jnp.int32),
      lengths.astype(jnp.int32), qf, k_pages, v_pages)
    return out.reshape(R, Hkv, Sq, G, D).transpose(0, 2, 1, 3, 4)


# =============================================================================
# fused head-interleaved layout + explicit double-buffered page DMA
# =============================================================================
K_IDX, V_IDX = 0, 1   # interleave positions inside a fused page


def _fused_kernel(bt_ref, pos_ref, len_ref,   # scalar prefetch [R,n],[R],[R]
                  q_ref, kv_hbm,              # [1,1,bq*G,D], [Hkv,P,2,ps,D]
                  *refs,                      # outputs, then (scratch, sem)
                  scale: float, window: int, softcap: float,
                  page_size: int, num_pages: int, block_q: int, group: int,
                  partial: bool, dma_depth: int):
    if partial:
        o_ref, m_out, l_out = refs[0], refs[1], refs[2]
        scratch, sem = refs[3], refs[4]
    else:
        o_ref, m_out, l_out = refs[0], None, None
        scratch, sem = refs[1], refs[2]
    r = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    BG, D = q_ref.shape[2], q_ref.shape[3]

    length = len_ref[r]
    pos = pos_ref[r]
    # live page range for this q tile: pages past ceil(len/ps), entirely
    # above the causal diagonal, or entirely below the sliding window are
    # never copied in at all (the grid-pipelined kernel only skipped their
    # FLOPs). ``pos``/``length`` may be shard-local (and negative): floor
    # division keeps the bounds exact either way.
    pages_needed = (length + page_size - 1) // page_size
    causal_hi = (pos + (qi + 1) * block_q - 1) // page_size + 1
    j_hi = jnp.minimum(jnp.minimum(pages_needed, causal_hi), num_pages)
    if window > 0:
        j_lo = jnp.maximum(
            (pos + qi * block_q - window + 1) // page_size, 0)
    else:
        j_lo = jnp.zeros_like(j_hi)
    j_lo = jnp.minimum(j_lo, jnp.maximum(j_hi, 0))

    def dma(slot, j):
        # one async copy moves the page's K and V planes together.
        return pltpu.make_async_copy(
            kv_hbm.at[h, bt_ref[r, j]], scratch.at[slot], sem.at[slot])

    # warmup: fill the ring — up to depth-1 copies in flight before the
    # loop's first wait (depth 2 reduces to the classic single ping).
    for i in range(dma_depth - 1):
        @pl.when(j_lo + i < j_hi)
        def _warmup(i=i):
            dma(jax.lax.rem(j_lo + i, dma_depth), j_lo + i).start()

    def body(j, carry):
        m_prev, l_prev, acc_prev = carry
        slot = jax.lax.rem(j, dma_depth)
        # overlap: start page j+depth-1's copy into the slot freed at
        # iteration j-1, then block on page j and compute while the ring's
        # depth-1 outstanding copies fly.
        nxt = j + dma_depth - 1
        @pl.when(nxt < j_hi)
        def _prefetch_next():
            dma(jax.lax.rem(nxt, dma_depth), nxt).start()
        dma(slot, j).wait()
        k = scratch[slot, K_IDX]                         # [ps, D]
        v = scratch[slot, V_IDX]
        q = q_ref[0, 0]                                  # [bq*G, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq*G, ps]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        q_pos = pos + qi * block_q + t
        k_pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (k_pos <= q_pos) & (k_pos < length)
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        # [bq*G, 1] row statistics: see the decode kernel.
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc_prev * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        j_lo, j_hi, body,
        (jnp.full((BG, 1), NEG_INF, jnp.float32),
         jnp.zeros((BG, 1), jnp.float32), jnp.zeros((BG, D), jnp.float32)))
    if partial:
        o_ref[0, 0] = acc
        m_out[0, 0] = m
        l_out[0, 0] = l
    else:
        o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_prefill_attention_fused(
    q: jnp.ndarray,             # [R, Sq, Hkv, G, D] chunk queries
    kv_pages: jnp.ndarray,      # [Hkv, P_total, 2, page_size, D]
    block_tables: jnp.ndarray,  # [R, num_pages] int32
    row_pos: jnp.ndarray,       # [R] int32 cache offset per row
    lengths: jnp.ndarray,       # [R] int32 post-chunk valid length per row
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    partial: bool = False,
    dma_depth: int = 2,
    interpret: bool = False,
):
    """Fused-layout ragged chunked prefill with ring-buffered page DMA.

    ``dma_depth`` sets the VMEM page-copy ring depth: depth N keeps up to
    N-1 copies in flight behind the page being computed (2 = the classic
    ping-pong double buffer). Output is bit-identical across depths.

    ``partial=False`` returns ``[R, Sq, Hkv, G, D]`` (the oracle's contract).
    ``partial=True`` returns the un-normalized flash state
    ``(acc [R,Sq,Hkv,G,D] f32, m [R,Sq,Hkv,G] f32, l [R,Sq,Hkv,G] f32)``;
    ``row_pos``/``lengths`` may then be shard-local (global minus the
    shard's key offset) — every mask depends only on position differences.
    Finalizing the partials matches ``partial=False`` bit-exactly.
    """
    R, Sq, Hkv, G, D = q.shape
    _, _, two, page_size, _ = kv_pages.shape
    assert two == 2, kv_pages.shape
    assert dma_depth >= 2, dma_depth
    num_pages = block_tables.shape[1]
    block_q = min(block_q, Sq)
    assert Sq % block_q == 0, (Sq, block_q)
    nq = Sq // block_q

    # [R, Hkv, Sq*G, D]: token t's G grouped heads are rows [t*G, (t+1)*G)
    qf = q.transpose(0, 2, 1, 3, 4).reshape(R, Hkv, Sq * G, D)

    kernel = functools.partial(
        _fused_kernel, scale=scale, window=window, softcap=softcap,
        page_size=page_size, num_pages=num_pages, block_q=block_q, group=G,
        partial=partial, dma_depth=dma_depth)

    if partial:
        # trailing singleton on m/l keeps the block legal for the TPU
        # compiler (see the decode kernel).
        stat = pl.BlockSpec((1, 1, block_q * G, 1),
                            lambda r, h, i, bt, pos, L: (r, h, i, 0))
        out_shape = (
            jax.ShapeDtypeStruct((R, Hkv, Sq * G, D), jnp.float32),
            jax.ShapeDtypeStruct((R, Hkv, Sq * G, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, Hkv, Sq * G, 1), jnp.float32))
        out_specs = (
            pl.BlockSpec((1, 1, block_q * G, D),
                         lambda r, h, i, bt, pos, L: (r, h, i, 0)),
            stat, stat)
    else:
        out_shape = jax.ShapeDtypeStruct((R, Hkv, Sq * G, D), q.dtype)
        out_specs = pl.BlockSpec((1, 1, block_q * G, D),
                                 lambda r, h, i, bt, pos, L: (r, h, i, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, Hkv, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q * G, D),
                         lambda r, h, i, bt, pos, L: (r, h, i, 0)),
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
        name="paged_prefill_attention",
    )(block_tables.astype(jnp.int32), row_pos.astype(jnp.int32),
      lengths.astype(jnp.int32), qf, kv_pages)

    def _rows(x):   # [R, Hkv, Sq*G, ...] -> [R, Sq, Hkv, G, ...]
        shp = (R, Hkv, Sq, G) + x.shape[3:]
        order = (0, 2, 1, 3) + tuple(range(4, len(shp)))
        return x.reshape(shp).transpose(order)

    if partial:
        acc, m, l = out
        return _rows(acc), _rows(m[..., 0]), _rows(l[..., 0])
    return _rows(out)
