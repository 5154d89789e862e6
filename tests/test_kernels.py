"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention

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
