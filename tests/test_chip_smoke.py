"""``chip_smoke.py``'s engine probe on the CPU, on the reference backend:
it calls the engine's private decode-argument builder, so a change of that
builder's signature fails here rather than on the chip."""
import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.request import Kind, Request
from repro.engine.engine import ServingEngine
from repro.models.model import build_model

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_first_decode_logits_match_the_next_decode_step(chip_smoke):
    cfg = get_config("qwen2.5-7b").reduced()
    model = build_model(cfg, remat=False)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        num_pages=64, page_size=chip_smoke.PAGE,
                        decode_buckets=(4,), backend="ref")
    rids = []
    for i in range(3):
        req = Request(Kind.OFFLINE, 0.0, 20, 4)
        eng.add_request(req, [(7 * i + j) % cfg.vocab_size + 1
                              for j in range(20)])
        eng.mixed_step([], req.rid, chunk_tokens=20)
        rids.append(req.rid)
    logits = chip_smoke.first_decode_logits(eng, rids)
    assert logits.shape == (3, cfg.vocab_size)
    assert np.isfinite(logits).all()
    # the probe writes the KV the next decode step writes again: greedy
    # decoding then picks the probe's argmax for every row
    nxt = eng.decode_step(rids)
    assert [nxt[r] for r in rids] == logits.argmax(-1).tolist()
