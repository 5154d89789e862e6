"""The seeded weights and the plain reference: the reference regenerates
the program's weights bit for bit, and computes what the program's own
float32 forward pass computes for both Qwen variants."""

# benchtest first: it puts bench/ and src/ on the import path
from benchtest import BENCH, TINY_MODEL
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import serve, spec, weights

REF = spec.load_module(BENCH / "configs" / "dense_gqa.py")
SEED = 2 ** 33 + 17


def _flat(tree, prefix=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _flat(v, prefix + (k,))
    else:
        yield prefix, tree


def test_layer_by_layer_weights_are_the_whole_model_weights():
    m = TINY_MODEL
    sp = REF.param_spec(m)
    full = dict(_flat(weights.make_params(sp, SEED, m["num_layers"],
                                          jnp.bfloat16)))
    for i in range(m["num_layers"]):
        lp = weights.layer_params(sp, SEED, i, jnp.bfloat16)
        for path, a in lp.items():
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(full[path][i],
                                                     np.float32))
    np.testing.assert_array_equal(
        np.asarray(weights.leaf(sp, SEED, ("lm_head",), jnp.bfloat16),
                   np.float32), np.asarray(full[("lm_head",)], np.float32))
    other = dict(_flat(weights.make_params(sp, SEED + 1, m["num_layers"],
                                           jnp.bfloat16)))
    assert not np.array_equal(np.asarray(other[("embed",)], np.float32),
                              np.asarray(full[("embed",)], np.float32))
    std = float(np.asarray(full[("layers", "mlp", "up", "w")],
                           np.float32).std())
    assert std == pytest.approx(0.02, rel=0.02)


@pytest.mark.parametrize("variant", [{"attn_bias": True, "qk_norm": False},
                                     {"attn_bias": False, "qk_norm": True}])
def test_reference_matches_the_programs_float32_forward(variant):
    from repro.models.model import build_model
    m = dict(TINY_MODEL, **variant, dtype="float32")
    cfg = serve.model_config(m)
    model = build_model(cfg, remat=False)
    params = weights.make_params(REF.param_spec(m), SEED, m["num_layers"],
                                 jnp.float32)
    serve.check_params(params, model)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, m["vocab_size"], 300).astype(np.int32)
    rows = [0, 7, 150, 299]
    batch = {"tokens": jnp.asarray(np.tile(toks, (len(rows), 1))),
             "lengths": jnp.asarray(np.array(rows) + 1, jnp.int32)}
    with jax.default_matmul_precision("highest"):
        want, _ = model.prefill(params, batch)
    got = REF.logits(m, SEED, [list(toks)], [rows])[0]
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)


def test_fp8_control_moves_the_logits():
    m = TINY_MODEL
    toks = list(np.random.default_rng(1).integers(0, m["vocab_size"], 64))
    ref = REF.logits(m, SEED, [toks], [list(range(64))])[0]
    ctl = REF.logits(m, SEED, [toks], [list(range(64))], quant="fp8")[0]
    rel = np.linalg.norm(ctl - ref) / np.linalg.norm(ref)
    assert 1e-3 < rel < 0.5
