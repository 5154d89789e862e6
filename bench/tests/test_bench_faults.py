"""The harness end to end on the CPU at a tiny size, past its look for a
chip: a sound run is correct, and a run whose timed path alters a token
where the engine produces it is not. The fp8 control, put in the program's
place, reads above the limit too (the chip-size readings are in PERF.md)."""

# benchtest first: it puts bench/ and src/ on the import path
from benchtest import TINY_MODEL, tiny_root  # noqa: F401
import argparse
import json

import numpy as np
import pytest

import calibrate
import run
from harness import check, spec

#: the tiny cell's limit (benchtest.make_root): its sound runs read 0 to
#: 3e-3, the fp8 control 0.04 to 0.07 over a run's few dozen served tokens
#: (four seeds each, CPU)
LIMIT = 0.01

ARGS = ["--workload", "tiny.colocate", "--seconds", "6", "--trace", "0"]


def _main(root, seed):
    return run.main(ARGS + ["--seed", str(seed)], root=root,
                    src=run.ROOT / "src", require_tpu=False,
                    compile_cache=False)


def test_sound_run_is_correct(tiny_root, capsys):
    r = _main(tiny_root, 2 ** 31 + 3)
    assert r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] < LIMIT
    assert set(r["metrics"]) == {"setup_s", "offline_tokens_per_s"}
    out = capsys.readouterr()
    assert out.err.strip().splitlines()[-1].startswith("check ")
    last = out.out.strip().splitlines()[-1]
    assert last.startswith('{"correct": true') and last.endswith("}}}")


def test_a_token_altered_where_produced_is_not_correct(tiny_root):
    with calibrate.altered_tokens(3, 512):
        r = _main(tiny_root, 12)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > LIMIT


def test_calibration_reads_sound_runs_below_the_limit(tiny_root):
    args = argparse.Namespace(workload="tiny.colocate", seeds=[5],
                              seconds=6.0, fault=False)
    (row,) = calibrate.readings(args, root=tiny_root, src=run.ROOT / "src",
                                require_tpu=False, compile_cache=False)
    assert row["served_gap"] < LIMIT
    assert row["fp8_control_gap"] is not None and row["tokens"] > 0


@pytest.mark.parametrize("seed", [3, 4, 2 ** 32 + 5])
def test_fp8_control_reads_above_the_limit(seed):
    """The control at the tiny size, on three seeds: the tokens fp8 puts
    first, read under the float32 reference at every position of random
    sequences, lie further below its best than the limit allows."""
    ref = spec.load_module(run.HERE / "configs" / "dense_gqa.py")
    rng = np.random.default_rng(seed % 2 ** 32)
    seqs = [list(rng.integers(0, 512, 512)) for _ in range(3)]
    rows = [list(range(512))] * 3
    want = ref.logits(TINY_MODEL, seed, seqs, rows)
    ctl = ref.logits(TINY_MODEL, seed, seqs, rows, quant="fp8")
    firsts = [list(np.argmax(c, axis=1)) for c in ctl]
    assert check.gaps(want, firsts).max() > LIMIT


def test_fp8_control_in_the_programs_place_is_not_correct(tiny_root):
    """``--control fp8``: the run compares, at each served position, the
    token the fp8 reference puts first, and comes out not correct."""
    r = run.main(ARGS + ["--seed", "2", "--control", "fp8"], root=tiny_root,
                 src=run.ROOT / "src", require_tpu=False, compile_cache=False)
    assert not r["correct"]
    assert r["checks"]["logit_gap"]["value"] > LIMIT


def test_warm_up_is_the_committed_list_only(tiny_root, capsys):
    """What one run met beyond the committed list is recorded beside the
    compile cache and printed, and the next run warms no more for it."""
    warmed = []
    for seed in (21, 22):
        _main(tiny_root, seed)
        line = next(x for x in capsys.readouterr().out.splitlines()
                    if x.startswith("[bench] warmed "))
        warmed.append(int(line.split()[2]))
    assert warmed[0] == warmed[1]
    met = tiny_root / ".bench_cache" / "shapes" / "tiny.colocate.json"
    assert len(json.loads(met.read_text())) > warmed[0]


def test_no_tpu_means_no_result(tiny_root, capsys):
    with pytest.raises(SystemExit):
        run.main(ARGS + ["--seed", "1"], root=tiny_root,
                 src=run.ROOT / "src", compile_cache=False)
    assert "correct" not in capsys.readouterr().out
