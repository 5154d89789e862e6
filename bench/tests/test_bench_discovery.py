"""A cell, configuration, traffic mix and metric added as new files and
BENCHMARK.json entries are found by name, with no file of the harness
changed."""

# benchtest first: it puts bench/ and src/ on the import path
from benchtest import make_root
import json

from harness import spec


def _add(root, mix_name="bursty2", metric="queued_online_at_close"):
    b = root / "bench"
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["name"] = "tiny-wide"
    cfg["model"] = dict(cfg["model"], name="tiny-wide", d_ff=1024)
    (b / "configs" / "tiny-wide.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "tiny.json").read_text())
    mix["online"]["rate_per_s"] = 7.0
    (b / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
    (b / "metrics" / f"{metric}.py").write_text(
        "def read(run):\n    return 42.0\n")
    s = json.loads((root / "BENCHMARK.json").read_text())
    s["configs"].append({"name": "tiny-wide", "source": "test",
                         "file": "bench/configs/tiny-wide.json",
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "tiny-wide.bursty", "config": "tiny-wide",
                           "traffic": mix_name, "chips": 1, "why": "test"})
    s["per_layer"].append({"name": metric, "unit": "1", "better": "lower",
                           "source": "program_counter", "layer": "scheduler",
                           "moves": "offline_tokens_per_s",
                           "workloads": ["tiny-wide.bursty"]})
    s["end_to_end"] = [dict(m, workloads=m["workloads"] + ["tiny-wide.bursty"])
                       for m in s["end_to_end"]]
    (root / "BENCHMARK.json").write_text(json.dumps(s))


def test_new_files_are_found_by_name(tmp_path):
    root = make_root(tmp_path)
    _add(root)
    cell = spec.load_cell(root, "tiny-wide.bursty")
    assert cell.model["d_ff"] == 1024
    assert cell.mix["online"]["rate_per_s"] == 7.0
    assert cell.reference().param_spec(cell.model)
    names = [m["name"] for m in cell.per_layer]
    assert names == ["queued_online_at_close"]
    assert spec.reader(root, "queued_online_at_close")(None) == 42.0
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                     "offline_tokens_per_s"}
    # the cell that was there reports what it did before
    old = spec.load_cell(root, "tiny.colocate")
    assert "queued_online_at_close" not in [m["name"] for m in old.per_layer]


def test_a_metric_without_workloads_follows_what_it_moves(tmp_path):
    e2e = [{"name": "a", "workloads": ["x"]}, {"name": "b"}]
    assert spec.reports({"name": "m", "moves": "a"}, "x", e2e)
    assert not spec.reports({"name": "m", "moves": "a"}, "y", e2e)
    assert spec.reports({"name": "m", "moves": "b"}, "y", e2e)
