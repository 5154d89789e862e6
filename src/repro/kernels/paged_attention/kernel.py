"""Paged decode attention Pallas TPU kernel.

One new query token per request attends over its KV cache stored in a paged
pool (non-contiguous pages, vLLM-style). The per-request page list (block
table) and the lengths are *scalar-prefetch* operands; the K/V pools stay in
HBM (``memory_space=pl.ANY``) and the kernel copies pages into VMEM itself
with ``make_async_copy``, one copy per page by table lookup — the TPU-native
equivalent of the pointer chasing a CUDA paged-attention kernel does.

grid = (B,), sequential. Row b reads only the ``cdiv(length, page)`` pages
that cover its length, ``pages_per_block`` pages per block; a page wholly
past the length costs neither a copy nor compute, so the padding of a table
(the batch's longest, rounded up) is free. Blocks are double-buffered: the
copies of the next block — the next row's first block after a row's last —
start before the current block is computed. Online-softmax accumulators
live in VMEM scratch, in f32, one set per KV head.

The pool is head-major, (num_pages, Hkv, page_size, hd): one page of all
heads is contiguous, so each copy moves a whole (Hkv, page, hd) page, and a
block in VMEM is (pages_per_block, Hkv, page, hd). The tail of a block past
the length (stale or never-written VMEM) is masked out of both the scores
and the values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: tokens one block aims at, and the VMEM its four page buffers (K and V,
#: double-buffered) may take. At qwen2.5-7b's decode shape on a v5e, 512
#: tokens ran a call in 0.73x the time of 256 and 128 (PERF.md).
BLOCK_TOKENS = 512
BLOCK_VMEM_BYTES = 4 << 20
LANES = 128


def pages_per_block(pages_per_req: int, page_size: int, num_kv_heads: int,
                    head_dim: int, itemsize: int) -> int:
    """Pages one block copies and computes, from static shapes only: about
    ``BLOCK_TOKENS`` tokens within ``BLOCK_VMEM_BYTES``, at most the
    table's width."""
    lanes = -(-head_dim // LANES) * LANES
    page_bytes = num_kv_heads * page_size * lanes * itemsize
    return max(1, min(BLOCK_TOKENS // page_size,
                      BLOCK_VMEM_BYTES // (4 * page_bytes), pages_per_req))


def _kernel(lengths_ref, tables_ref,  # scalar prefetch
            q_ref, k_hbm, v_hbm,      # q block, whole pools in HBM
            o_ref,
            k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr, *,
            page_size: int, pages_per_req: int, ppb: int,
            logit_softcap: float, scale: float):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    num_kv_heads = q_ref.shape[1]
    hd = q_ref.shape[3]
    bk = ppb * page_size

    def live_pages(row):
        return jnp.minimum((lengths_ref[row] + page_size - 1) // page_size,
                           pages_per_req)

    def copy_block(row, blk, slot, live, *, wait: bool):
        """Start (or wait for) the copies of the pages of block ``blk`` of
        ``row`` that hold live tokens (``live`` of the row's pages do)
        into buffer ``slot``."""
        first = row * pages_per_req + blk * ppb

        def page_copy(j, carry):
            page = tables_ref[first + j]
            for kv, (pool, buf) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf))):
                c = pltpu.make_async_copy(pool.at[page], buf.at[slot, j],
                                          sems.at[kv, slot])
                c.wait() if wait else c.start()
            return carry

        # a rolled loop: unrolled, the copies made the kernel's v5e
        # executable 1.5 MB (0.4 MB rolled), and every step program that
        # holds it slower to load
        jax.lax.fori_loop(0, jnp.minimum(live - blk * ppb, ppb), page_copy, 0)

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0

    length = lengths_ref[b]
    live = live_pages(b)
    blocks = (live + ppb - 1) // ppb
    # the previous row, if it ran a block, started this row's first one
    prev_ran = jnp.logical_and(b > 0,
                               lengths_ref[jnp.maximum(b - 1, 0)] > 0)

    @pl.when(jnp.logical_and(blocks > 0, jnp.logical_not(prev_ran)))
    def _start_first():
        copy_block(b, 0, slot_ref[0], live, wait=False)

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(i, slot):
        nxt = 1 - slot

        @pl.when(i + 1 < blocks)
        def _next_block():
            copy_block(b, i + 1, nxt, live, wait=False)

        nrow = jnp.minimum(b + 1, nb - 1)

        @pl.when(jnp.logical_and(
            i + 1 == blocks,
            jnp.logical_and(b + 1 < nb, lengths_ref[nrow] > 0)))
        def _next_row():
            copy_block(nrow, 0, nxt, live_pages(nrow), wait=False)

        copy_block(b, i, slot, live, wait=True)
        first_pos = i * bk
        s_valid = (first_pos + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)) < length
        v_valid = (first_pos + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0)) < length
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)                        # (G, hd)
            k = k_buf[slot, :, h].astype(jnp.float32).reshape(bk, hd)  # (bk, hd)
            v = v_buf[slot, :, h].astype(jnp.float32).reshape(bk, hd)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ()))) * scale                # (G, bk)
            if logit_softcap:
                s = jnp.tanh(s / logit_softcap) * logit_softcap
            s = jnp.where(s_valid, s, NEG_INF)
            v = jnp.where(v_valid, v, 0.0)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_scr[h] = m_new
        return nxt

    slot_ref[0] = jax.lax.fori_loop(0, blocks, block, slot_ref[0])
    o_ref[0] = (acc_scr[...] /
                jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, block_tables, lengths, *,
                           logit_softcap: float = 0.0, scale: float,
                           interpret: bool = False):
    """q: (B, Hkv, G, hd) — grouped query heads.
    k_pages/v_pages: (num_pages, Hkv, page_size, hd) head-major paged KV.
    block_tables: (B, pages_per_req) int32 page ids (garbage past length ok).
    lengths: (B,) int32 valid tokens per request.
    Returns (B, Hkv, G, hd)."""
    B, Hkv, G, hd0 = q.shape
    # Mosaic slices an HBM page only along whole 128-lane tiles: a narrower
    # head dim is zero-padded (exact for the scores and the values), which
    # the tiled HBM layout holds padded anyway
    lanes = -hd0 % LANES
    if lanes:
        pad = ((0, 0),) * 3 + ((0, lanes),)
        q, k_pages, v_pages = (jnp.pad(a, pad) for a in (q, k_pages, v_pages))
    hd = hd0 + lanes
    page_size = k_pages.shape[2]
    pages_per_req = block_tables.shape[1]
    ppb = pages_per_block(pages_per_req, page_size, Hkv, hd,
                          k_pages.dtype.itemsize)

    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_req=pages_per_req, ppb=ppb,
        logit_softcap=logit_softcap, scale=scale)
    row = pl.BlockSpec((1, Hkv, G, hd), lambda b, lens, tabs: (b, 0, 0, 0))
    page_buf = pltpu.VMEM((2, ppb, Hkv, page_size, hd), k_pages.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[row,
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            page_buf,                              # K pages, 2 slots
            page_buf,                              # V pages, 2 slots
            pltpu.SemaphoreType.DMA((2, 2)),       # (K|V, slot)
            pltpu.SMEM((1,), jnp.int32),           # slot of the next block
            pltpu.VMEM((Hkv, G, 1), jnp.float32),  # running max
            pltpu.VMEM((Hkv, G, 1), jnp.float32),  # running sum
            pltpu.VMEM((Hkv, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(lengths, block_tables.reshape(-1), q, k_pages, v_pages)
    return out[..., :hd0]
