"""Readings that the correctness limits are set from: for each seed, one run
of the cell as ``run.py`` makes it, then the widest logit gap of the served
tokens under the float32 reference (the sound reading), the widest gap of
the tokens that the fp8 control puts first at the same positions (the
control's reading) and, with ``--fault``, the served gap of a run in which
one token in 50 is altered where the engine produces it.

    python3 bench/calibrate.py --workload qwen2.5-7b.offline \
        --seeds 11,12,13 --seconds 20 [--fault]

Seeds run one after another in one process, sharing the step programs.
Prints one JSON line per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import run  # noqa: E402


@contextlib.contextmanager
def altered_tokens(every: int, vocab: int):
    """A token altered where it is produced: every ``every``-th token the
    engine appends to a request's record is replaced by the next id."""
    from repro.engine import engine as eng
    orig = eng.TokenRing.append
    count = [0]

    def append(self, tok):
        count[0] += 1
        if count[0] % every == 0:
            tok = (int(tok) + 1) % vocab
        return orig(self, tok)
    eng.TokenRing.append = append
    try:
        yield
    finally:
        eng.TokenRing.append = orig


def readings(args, root=run.ROOT, src=None, require_tpu=True,
             compile_cache=True) -> list[dict]:
    root = Path(root)
    src = Path(src) if src is not None else root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from harness import check, serve, spec, warmup
    cell = spec.load_cell(root, args.workload)
    cache_dir = root / ".bench_cache"
    jax = run.setup_jax(cache_dir if compile_cache else None)
    devices, _ = run.check_device(jax, cell.chips, require_tpu)
    counter = warmup.CompileCounter()
    keep: dict = {}
    out = []
    try:
        for seed in args.seeds:
            fault = (altered_tokens(50, cell.model["vocab_size"])
                     if args.fault
                     else contextlib.nullcontext())
            with fault:
                data = serve.run(cell, seed, args.seconds,
                                 t_start=time.perf_counter(),
                                 device=devices[0], trace_dir=None,
                                 trace_s=0.0, cache_dir=cache_dir,
                                 counter=counter, log=run.log, keep=keep)
            picked = check.sample(data, seed)
            gap, ctl = (check.served_gap(cell.reference(), cell.model, seed,
                                         picked, quant="fp8")
                        if picked else (None, None))
            row = {"seed": seed, "fault": bool(args.fault),
                   "served_gap": gap, "fp8_control_gap": ctl,
                   "requests": len(picked),
                   "tokens": sum(len(r.tokens) for r in picked),
                   "longest": max((len(r.tokens) for r in picked), default=0),
                   "compiles_in_window": data.compiles[0],
                   "setup_s": data.setup_s}
            print(json.dumps(row), flush=True)
            out.append(row)
    finally:
        counter.close()
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", action="store_true")
    return ap.parse_args(argv)


if __name__ == "__main__":
    try:
        readings(parse())
    except run.NoDevice as e:
        print(e, file=sys.stderr)
        sys.exit(2)
