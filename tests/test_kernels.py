"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.paged_attention.kernel import (pages_per_block,
                                                  paged_attention_pallas)
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref

FLASH_CASES = [
    # B, Sq, Skv, Hq, Hkv, hd, causal, window, softcap
    (2, 128, 128, 4, 2, 64, True, 0, 0.0),
    (1, 256, 256, 8, 8, 128, True, 0, 50.0),
    (2, 128, 128, 4, 1, 64, True, 64, 0.0),
    (1, 100, 100, 2, 2, 32, True, 0, 0.0),       # non-multiple-of-block
    (2, 64, 192, 4, 4, 64, False, 0, 0.0),       # encoder (non-causal)
    (1, 64, 64, 2, 2, 48, True, 16, 30.0),       # window + softcap
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_vs_ref(case, dtype, rng):
    B, Sq, Skv, Hq, Hkv, hd, causal, window, cap = case
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          logit_softcap=cap, interpret=True,
                          block_q=64, block_kv=64)
    ref = flash_attention(q, k, v, causal=causal, window=window,
                          logit_softcap=cap, use_ref=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


PAGED_CASES = [
    # B, Hq, Hkv, hd, page, P, npages, softcap
    (2, 4, 2, 64, 16, 4, 32, 0.0),
    (3, 8, 8, 128, 8, 6, 64, 50.0),
    (1, 4, 1, 32, 32, 2, 8, 0.0),
    (4, 2, 2, 64, 4, 8, 64, 0.0),
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_vs_ref(case, dtype, rng):
    B, Hq, Hkv, hd, page, P, npages, cap = case
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, Hq, hd), dtype)
    kp = jax.random.normal(ks[1], (npages, Hkv, page, hd), dtype)
    vp = jax.random.normal(ks[2], (npages, Hkv, page, hd), dtype)
    tables = jax.random.randint(ks[3], (B, P), 0, npages)
    lengths = jax.random.randint(ks[3], (B,), 1, P * page + 1)
    out = paged_attention(q, kp, vp, tables, lengths, num_kv_heads=Hkv,
                          logit_softcap=cap, interpret=True)
    ref = paged_attention(q, kp, vp, tables, lengths, num_kv_heads=Hkv,
                          logit_softcap=cap, use_ref=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_paged_attention_ignores_garbage_past_length(rng):
    """Pages past `length` must not affect the output (masking contract)."""
    B, Hkv, hd, page, P, npages = 1, 2, 32, 8, 4, 16
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, 2, hd))
    kp = jax.random.normal(ks[1], (npages, Hkv, page, hd))
    vp = jax.random.normal(ks[2], (npages, Hkv, page, hd))
    tables = jnp.array([[3, 7, 1, 2]], jnp.int32)
    lengths = jnp.array([11], jnp.int32)  # only pages 0-1 partially used
    out1 = paged_attention(q, kp, vp, tables, lengths, num_kv_heads=Hkv, use_ref=True)
    tables2 = jnp.array([[3, 7, 9, 14]], jnp.int32)  # garbage tail pages
    out2 = paged_attention(q, kp, vp, tables2, lengths, num_kv_heads=Hkv, use_ref=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


# Hkv, G, hd, P, softcap; page 16. Lengths are derived from the block the
# kernel picks: 1, a page edge, a block edge and the whole table.
PAGED_EDGE_CASES = [
    (4, 7, 128, 40, 0.0),     # qwen2.5 heads; P not a multiple of the block
    (4, 8, 64, 1, 0.0),       # tinyllama heads; a one-page table
    (2, 8, 64, 72, 0.0),      # hd 64 over three blocks, the last partial
    (2, 4, 256, 40, 50.0),    # gemma2's head dim and logit softcap
]


@pytest.mark.parametrize("case", PAGED_EDGE_CASES)
def test_paged_attention_block_edges_vs_ref(case, rng):
    """The kernel (interpret) against the oracle where its blocks end: at
    one token, a page edge, a block edge and the full table, over a
    shuffled table of distinct pages."""
    Hkv, G, hd, P, cap = case
    page = 16
    ppb = pages_per_block(P, page, Hkv, hd, itemsize=4)   # float32
    if P > 1:
        assert P % ppb, "the case must end in a partial block"
    lengths = jnp.array([1, page, min(ppb, P) * page, P * page], jnp.int32)
    B = len(lengths)
    npages = B * P + 3
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, Hkv * G, hd))
    kp = jax.random.normal(ks[1], (npages, Hkv, page, hd))
    vp = jax.random.normal(ks[2], (npages, Hkv, page, hd))
    tables = jax.random.permutation(ks[3], npages)[:B * P].reshape(B, P)
    tables = tables.astype(jnp.int32)
    out = paged_attention(q, kp, vp, tables, lengths, num_kv_heads=Hkv,
                          logit_softcap=cap, interpret=True)
    ref = paged_attention(q, kp, vp, tables, lengths, num_kv_heads=Hkv,
                          logit_softcap=cap, use_ref=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("tail", ["nan_pages", "outside_pool"])
def test_paged_attention_never_reads_pages_past_length(tail, rng):
    """Table entries past each row's last live page point at pool pages
    filled with NaN, or past the end of the pool. The kernel, run by the
    TPU interpreter (copies happen on wait, a read outside a buffer raises,
    never-written VMEM holds NaN), must not read them: its output stays
    finite and equals the oracle over a table whose tail is clean."""
    B, Hkv, G, hd, page, P = 3, 2, 4, 64, 16, 40
    lengths = jnp.array([1, 17, 33 * 16 + 5], jnp.int32)   # 1, 2, 34 pages
    clean = B * P
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, hd))
    kp = jax.random.normal(ks[1], (clean + B * P, Hkv, page, hd))
    vp = jax.random.normal(ks[2], (clean + B * P, Hkv, page, hd))
    kp = kp.at[clean:].set(jnp.nan)
    vp = vp.at[clean:].set(jnp.nan)
    if tail == "outside_pool":
        kp, vp = kp[:clean], vp[:clean]
    tables = np.arange(B * P, dtype=np.int32).reshape(B, P)
    live = (np.asarray(lengths) + page - 1) // page
    past = np.arange(P)[None] >= live[:, None]
    dirty = np.where(past, clean + tables, tables)   # NaN or outside
    out = paged_attention_pallas(
        q, kp, vp, jnp.asarray(dirty), lengths, scale=hd ** -0.5,
        interpret=pltpu.InterpretParams(out_of_bounds_reads="raise"))
    assert bool(jnp.isfinite(out).all())
    ref = paged_attention_ref(q, kp[:clean], vp[:clean], jnp.asarray(tables),
                              lengths, scale=hd ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
