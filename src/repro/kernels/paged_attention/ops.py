"""Jit'd public wrapper for paged decode attention.

Model layout in: q (B, Hq, hd) for the single new token per request; the
wrapper regroups GQA heads to (B, Hkv, G, hd) and dispatches to the Pallas
kernel (TPU / interpret) or the jnp oracle (CPU engine fallback).

Dispatch: pass ``backend="auto"|"pallas"|"interpret"|"ref"`` (preferred —
this is what the engine threads through), or the legacy ``use_ref``/
``interpret`` booleans directly.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from repro.kernels import backend_flags
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref


@functools.partial(jax.jit, static_argnames=("num_kv_heads", "logit_softcap",
                                             "interpret", "use_ref", "backend"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    num_kv_heads: int, logit_softcap: float = 0.0,
                    interpret: bool = False, use_ref: bool = False,
                    backend: str | None = None):
    """q: (B, Hq, hd); head-major pools (num_pages, Hkv, page, hd);
    block_tables (B, P) int32; lengths (B,). Returns (B, Hq, hd)."""
    if backend is not None:
        use_ref, interpret = backend_flags(backend)
    B, Hq, hd = q.shape
    G = Hq // num_kv_heads
    qg = q.reshape(B, num_kv_heads, G, hd)
    scale = 1.0 / np.sqrt(hd)
    fn = paged_attention_ref if use_ref else functools.partial(
        paged_attention_pallas, interpret=interpret)
    o = fn(qg, k_pages, v_pages, block_tables, lengths,
           logit_softcap=logit_softcap, scale=scale)
    return o.reshape(B, Hq, hd)
