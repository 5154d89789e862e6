"""Paged decode attention Pallas TPU kernel.

One new query token per request attends over its KV cache stored in a paged
pool (non-contiguous pages, vLLM-style). The per-request page list
(block table) is a *scalar-prefetch* operand: BlockSpec index_maps read it
to stream exactly the pages belonging to the request from HBM into VMEM —
the TPU-native equivalent of the gather a CUDA paged-attention kernel does
with pointer chasing (DESIGN.md §3, hardware adaptation).

grid = (B, Hkv, pages_per_req), last axis sequential; online-softmax
accumulators persist in VMEM scratch across page iterations. Pages past the
request length are skipped with pl.when (no HBM traffic is saved in
interpret mode, but on TPU the pipeline still fetches — production uses
num_pages-per-request grids; we keep the static bound and mask).

The pool is head-major, (num_pages, Hkv, page_size, hd), so one K/V block
is (1, 1, page_size, hd): Mosaic requires a block's last two dimensions to
be multiples of the (8, 128) tile or equal to the array's own, and a
(page_size, 1, hd) slice of a token-major pool breaks that for Hkv > 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref, tables_ref,  # scalar prefetch
            q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            page_size: int, pages_per_req: int, logit_softcap: float,
            scale: float):
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    page_start = pi * page_size

    @pl.when(page_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (page, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, page)
        if logit_softcap:
            s = jnp.tanh(s / logit_softcap) * logit_softcap
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(pi == pages_per_req - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, block_tables, lengths, *,
                           logit_softcap: float = 0.0, scale: float,
                           interpret: bool = False):
    """q: (B, Hkv, G, hd) — grouped query heads.
    k_pages/v_pages: (num_pages, Hkv, page_size, hd) head-major paged KV.
    block_tables: (B, pages_per_req) int32 page ids (garbage past length ok).
    lengths: (B,) int32 valid tokens per request.
    Returns (B, Hkv, G, hd)."""
    B, Hkv, G, hd = q.shape
    page_size = k_pages.shape[2]
    pages_per_req = block_tables.shape[1]

    kernel = functools.partial(
        _kernel, page_size=page_size, pages_per_req=pages_per_req,
        logit_softcap=logit_softcap, scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, pages_per_req),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, pi, lens, tabs: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, pi, lens, tabs: (tabs[b, pi], h, 0, 0)),
            pl.BlockSpec((1, 1, page_size, hd),
                         lambda b, h, pi, lens, tabs: (tabs[b, pi], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, pi, lens, tabs: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, block_tables, q, k_pages, v_pages)
