"""Causal flash-attention Pallas TPU kernel (prefill hot path).

Tiling: grid = (batch, q_heads, num_q_blocks, num_kv_blocks) with the last
axis sequential ("arbitrary") so the online-softmax accumulators live in
VMEM scratch across kv iterations. Blocks are (Qb, head_dim) / (Kb, head_dim)
tiles in VMEM; head_dim and block sizes should be multiples of 128 on real
hardware for MXU alignment (the ops wrapper pads).

Causal + sliding-window block skipping: kv blocks entirely outside the
causal/window band are skipped with pl.when — this is the triangular-skip
optimization the pure-XLA path cannot express (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            kv_len: int, q_offset: int, block_q: int, block_kv: int,
            num_kv_blocks: int, causal: bool, window: int,
            logit_softcap: float, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = q_offset + qi * block_q
    k_start = ki * block_kv
    # block-level skip: entirely in the future (causal) or past the window
    run = k_start < kv_len
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
        if window:
            run = jnp.logical_and(
                run, k_start + block_kv - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (Qb, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (Kb, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (Qb, Kb)
        if logit_softcap:
            s = jnp.tanh(s / logit_softcap) * logit_softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = k_pos < kv_len
        if causal:
            rel = q_pos - k_pos
            mask = jnp.logical_and(mask, rel >= 0)
            if window:
                mask = jnp.logical_and(mask, rel < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_prefill_pallas(q, k, v, *, kv_len: int, q_offset: int = 0,
                         causal: bool = True, window: int = 0,
                         logit_softcap: float = 0.0, scale: float,
                         block_q: int = 128, block_kv: int = 128,
                         interpret: bool = False):
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Skv, hd). Sq % block_q == 0,
    Skv % block_kv == 0 (ops wrapper pads). Returns (B, Hq, Sq, hd)."""
    B, Hq, Sq, hd = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq = Sq // block_q
    nk = Skv // block_kv

    kernel = functools.partial(
        _kernel, kv_len=kv_len, q_offset=q_offset, block_q=block_q,
        block_kv=block_kv, num_kv_blocks=nk, causal=causal, window=window,
        logit_softcap=logit_softcap, scale=scale)

    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
