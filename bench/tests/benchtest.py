"""Shared by the benchmark's CPU tests, which import it first: the
benchmark's modules import as top-level ``harness`` and ``run`` (as when
``python3 bench/run.py`` runs), the program from ``src``. A tiny cell,
built as files under a temporary root, stands in for the chip-sized ones.
(Not a ``conftest.py``: the repo's ``tests/conftest.py`` would shadow its
names in a pytest worker that collects both directories.)"""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"name": "tiny", "family": "dense", "num_layers": 2,
              "d_model": 256, "num_heads": 4, "num_kv_heads": 2, "d_ff": 512,
              "vocab_size": 512, "head_dim": 64, "attn_bias": True,
              "qk_norm": False, "rope_theta": 10000.0, "norm_eps": 1e-06,
              "dtype": "bfloat16", "tie_embeddings": False}

TINY_MIX = {"pools": {"strict": 1, "relaxed": 1},
            "slo": {"ttft_s": 2.0, "tpot_s": 0.5},
            "ramp_s": 2,
            "online": {"rate_per_s": 3.0, "prompt_mean": 40, "output_mean": 8,
                       "cov": 1.0, "prompt_cap": 128, "output_cap": 16,
                       "bursts": [{"at_frac": 0.4, "seconds": 1,
                                   "mult": 3.0}]},
            "offline": {"count": 400, "prompt_mean": 40, "output_mean": 12,
                        "cov": 1.0, "prompt_cap": 128, "output_cap": 32}}

#: KV pages per engine of the tiny cell (the CPU reports no memory limit to
#: size them from)
TINY_PAGES = 256


def make_root(tmp: Path, *, limit: float = 0.01, model=None) -> Path:
    """A checkout-shaped directory holding one tiny cell ``tiny.colocate``
    beside the real metric readers and reference."""
    b = tmp / "bench"
    (b / "configs").mkdir(parents=True)
    (b / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", b / "metrics")
    shutil.copy(BENCH / "configs" / "dense_gqa.py", b / "configs")
    (b / "configs" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "source": "test", "reference": "dense_gqa",
        "model": model or TINY_MODEL, "limits": {"logit_gap": limit}}))
    (b / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                        "file": "bench/configs/tiny.json", "why": "test"}]
    spec["workloads"] = [{"name": "tiny.colocate", "config": "tiny",
                          "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m["workloads"] = ["tiny.colocate"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    from harness import serve
    monkeypatch.setattr(serve, "pages_for_chip",
                        lambda *a, **k: TINY_PAGES)
    return make_root(tmp_path)
