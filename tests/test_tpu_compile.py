"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at published model widths.

Interpret mode runs the kernel bodies but never asks Mosaic whether it
accepts the block layouts; these compiles do, with no chip attached. The
topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU compiler library, and every
test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.engine.engine import ServingEngine
from repro.engine.kv_cache import _scatter_layers
from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention
from repro.models.model import build_model

# name: (Hq, Hkv, hd) — G = Hq / Hkv query heads per KV head
WIDTHS = {
    "tinyllama-1.1b": (32, 4, 64),    # G 8
    "qwen2.5-7b": (28, 4, 128),       # G 7
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library to describe a chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _assert_paged_kernel(compiled):
    """The paged decode kernel is one ``tpu_custom_call`` instruction named
    ``paged_attention``: the name the benchmark's roofline reader sums."""
    names = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
                       compiled.as_text())
    assert [n.rsplit(".", 1)[0] for n in names] == ["paged_attention"], names


@pytest.mark.parametrize("arch", list(WIDTHS))
def test_paged_decode_compiles_for_v5e(arch, one_chip):
    Hq, Hkv, hd = WIDTHS[arch]
    B, P, page = 8, 8, 16     # one decode bucket, its compacted pages
    lowered = paged_attention.lower(
        _spec((B, Hq, hd), jnp.bfloat16, one_chip),
        _spec((B * P, Hkv, page, hd), jnp.bfloat16, one_chip),
        _spec((B * P, Hkv, page, hd), jnp.bfloat16, one_chip),
        _spec((B, P), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        num_kv_heads=Hkv)
    _assert_paged_kernel(lowered.compile())


@pytest.mark.parametrize("P", [1, 1024])
@pytest.mark.parametrize("arch", list(WIDTHS))
def test_paged_decode_compiles_at_extreme_tables_for_v5e(arch, P, one_chip):
    """A decode bucket of 8 at the offline cell's shortest and longest
    padded tables (1 and 1,024 pages): the kernel's block of pages is
    clamped to the table, and it stays one custom call the roofline reader
    can find by name."""
    Hq, Hkv, hd = WIDTHS[arch]
    B, page = 8, 16
    lowered = paged_attention.lower(
        _spec((B, Hq, hd), jnp.bfloat16, one_chip),
        _spec((B * P, Hkv, page, hd), jnp.bfloat16, one_chip),
        _spec((B * P, Hkv, page, hd), jnp.bfloat16, one_chip),
        _spec((B, P), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip),
        num_kv_heads=Hkv)
    _assert_paged_kernel(lowered.compile())


@pytest.mark.parametrize("arch", list(WIDTHS))
def test_flash_prefill_compiles_for_v5e(arch, one_chip):
    Hq, Hkv, hd = WIDTHS[arch]
    S = 256
    lowered = flash_attention.lower(
        _spec((1, S, Hq, hd), jnp.bfloat16, one_chip),
        _spec((1, S, Hkv, hd), jnp.bfloat16, one_chip),
        _spec((1, S, Hkv, hd), jnp.bfloat16, one_chip))
    _assert_kernel(lowered.compile())


@pytest.fixture(scope="module")
def tinyllama_steps(one_chip):
    """A full-width tinyllama-1.1b engine whose step functions are lowered
    for the described chip, plus its weight specs. The steps take the
    weights as arguments: a placeholder array only tells the engine where
    its (tiny, unused) CPU pools live."""
    cfg = get_config("tinyllama-1.1b")
    model = build_model(cfg, remat=False)
    eng = ServingEngine(model, {"embed": jnp.zeros(1)}, num_pages=2,
                        backend="pallas")
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, one_chip),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    return cfg, eng, params


def _pool_bytes(cfg, num_pages):
    """Bytes of one bf16 (L, pages, page, Hkv*hd) pool."""
    return cfg.num_layers * num_pages * 16 * cfg.num_kv_heads * cfg.head_dim_ * 2


def _lower_step(kind, tinyllama_steps, chip, num_pages):
    """Lower one engine step kind over two bf16 pools of ``num_pages``."""
    cfg, eng, params = tinyllama_steps
    pool = _spec((cfg.num_layers, num_pages, 16,
                  cfg.num_kv_heads * cfg.head_dim_), jnp.bfloat16, chip)

    def i32(*shape):
        return _spec(shape, jnp.int32, chip)

    def f32(n):
        return _spec((n,), jnp.float32, chip)

    key, step = _spec((2,), jnp.uint32, chip), i32()
    B, P, K = 8, 8, 4
    if kind == "decode":
        return eng._decode_fn(B, P).lower(
            params, i32(B), i32(B), i32(B, P), i32(B), pool, pool, key, step,
            f32(B), i32(B))
    if kind == "horizon":
        return eng._horizon_fn(B, P, K).lower(
            params, i32(B), i32(B), i32(B, P), pool, pool, i32(B), key, step,
            f32(B), i32(B))
    if kind in ("mixed", "chunk_only"):
        b, p = (B, P) if kind == "mixed" else (0, 0)
        return eng._mixed_fn(b, p, 64, 4).lower(
            params, i32(b), i32(b), i32(b, p), i32(b), i32(64), i32(2), i32(4),
            pool, pool, key, step, f32(b + 1), i32(b + 1))
    if kind == "mixed_horizon":
        return eng._mixed_horizon_fn(B, P, 16, 4, K).lower(
            params, i32(B), i32(B), i32(B, P), pool, pool, i32(B),
            i32(K, 16), i32(K, 2), i32(4), key, step, f32(B + 1), i32(B + 1))
    assert kind == "prefill_scatter"
    kv = _spec((cfg.num_layers, 64, cfg.num_kv_heads, cfg.head_dim_),
               jnp.bfloat16, chip)
    return _scatter_layers.lower(pool, pool, i32(cfg.num_layers), i32(64),
                                 i32(64), kv, kv)


@pytest.mark.parametrize("kind", ["decode", "horizon", "mixed", "chunk_only",
                                  "mixed_horizon", "prefill_scatter"])
def test_engine_step_temporaries_do_not_grow_with_pool_for_v5e(
        kind, tinyllama_steps, one_chip):
    """Every full-width tinyllama-1.1b step kind, compiled for v5e at 2,048
    and at 8,192 pages, holds the same temporaries: the donated pools are
    written in place, never copied or relayouted."""
    temps = []
    for num_pages in (2048, 8192):
        compiled = _lower_step(kind, tinyllama_steps, one_chip,
                               num_pages).compile()
        if kind not in ("chunk_only", "prefill_scatter"):   # no decode rows
            _assert_kernel(compiled)
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
    cfg = tinyllama_steps[0]
    growth = _pool_bytes(cfg, 8192) - _pool_bytes(cfg, 2048)
    assert temps[1] - temps[0] < growth / 100, temps


def test_decode_step_updates_pools_in_place_for_v5e(one_chip, tinyllama_steps):
    """The whole tinyllama-1.1b decode step (22 layers, published widths)
    compiled for v5e over 4,096-page pools holds no pool-sized temporary:
    the donated pools are written in place. A pool whose minor dims are
    (Hkv, hd) = (4, 64) made the compiler relayout whole pools, about four
    pool copies of temporaries."""
    compiled = _lower_step("decode", tinyllama_steps, one_chip, 4096).compile()
    _assert_kernel(compiled)
    cfg = tinyllama_steps[0]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < _pool_bytes(cfg, 4096) / 16
