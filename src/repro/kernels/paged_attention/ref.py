"""Pure-jnp oracle for paged decode attention: gather pages, mask, softmax."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        logit_softcap: float = 0.0, scale: float | None = None):
    """Same signature/layout as the kernel: q (B, Hkv, G, hd),
    head-major pools (num_pages, Hkv, page, hd), tables (B, P), lengths (B,)."""
    B, Hkv, G, hd = q.shape
    page = k_pages.shape[2]
    P = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    # gather this batch's pages -> contiguous (B, P*page, Hkv, hd)
    k = k_pages[block_tables].swapaxes(2, 3).reshape(B, P * page, Hkv, hd)
    v = v_pages[block_tables].swapaxes(2, 3).reshape(B, P * page, Hkv, hd)
    s = jnp.einsum("bhgd,bchd->bhgc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if logit_softcap:
        s = jnp.tanh(s / logit_softcap) * logit_softcap
    valid = jnp.arange(P * page)[None] < lengths[:, None]
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhgc,bchd->bhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
