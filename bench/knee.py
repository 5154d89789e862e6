"""Finds a cell's knee once, by a sweep on the chip: the cell's traffic at
each given online base rate (the burst scales with it), one window each,
in one process. The knee is the highest rate at which the online backlog
does not grow: requests due in the second half of the window wait no
longer for their first token than those due in the first half, and none is
still waiting at the close.

    python3 bench/knee.py --workload qwen2.5-7b.colocate \
        --rates 0.5,1,1.5,2,3 --seconds 30 --seed 5
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import numpy as np

import run


def sweep(args) -> list[dict]:
    sys.path.insert(0, str(run.ROOT / "src"))
    from harness import measures, serve, spec, warmup
    cell = spec.load_cell(run.ROOT, args.workload)
    cache_dir = run.ROOT / ".bench_cache"
    jax = run.setup_jax(cache_dir)
    devices, _ = run.check_device(jax, cell.chips, True)
    counter = warmup.CompileCounter()
    keep: dict = {}
    rows = []
    base = copy.deepcopy(cell.mix)
    try:
        for rate in args.rates:
            cell.mix = copy.deepcopy(base)
            cell.mix["online"]["rate_per_s"] = rate
            d = serve.run(cell, args.seed, args.seconds,
                          t_start=time.perf_counter(), device=devices[0],
                          trace_dir=None, trace_s=0.0, cache_dir=cache_dir,
                          counter=counter, log=run.log, keep=keep)
            mid = (d.w0 + d.w1) / 2
            on = d.in_window("online")
            first = [r.times[0] - r.due for r in on
                     if r.due < mid and r.times and r.times[0] < d.w1]
            second = [r.times[0] - r.due for r in on
                      if r.due >= mid and r.times and r.times[0] < d.w1]
            waiting = sum(1 for r in on if not r.times or r.times[0] >= d.w1)
            row = {"rate": rate, "online_in_window": len(on),
                   "waiting_at_close": waiting,
                   "ttft_p50_first_half": float(np.median(first)) if first
                   else None,
                   "ttft_p50_second_half": float(np.median(second)) if second
                   else None,
                   "ttft_p90": measures.percentile(measures.ttfts(d), 90),
                   "tpot_p90": measures.percentile(measures.tpots(d), 90),
                   "offline_tokens_per_s": measures.tokens_in_window(
                       d, "offline") / d.seconds,
                   "slo_attainment": measures.slo_attainment(d),
                   "compiles_in_window": d.compiles[0]}
            print(json.dumps(row), flush=True)
            rows.append(row)
    finally:
        counter.close()
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    try:
        sweep(ap.parse_args())
    except run.NoDevice as e:
        print(e, file=sys.stderr)
        sys.exit(2)
