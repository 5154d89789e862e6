"""Runs one cell of the benchmark once, in one process, and prints its
result as the last line of standard output.

    python3 bench/run.py --workload qwen2.5-7b.offline --seed 7 \
        --seconds 45 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (with a profiler trace of part of the window). Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.

``--control fp8`` runs the correctness control: the comparison reads, in
place of each served token, the token that the reference computed in fp8
puts first at that position, so the run has to come out not correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class NoDevice(SystemExit):
    pass


def setup_jax(cache_dir: Path):
    """The persistent compile cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    import jax
    if cache_dir is not None and not os.environ.get(
            "JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(cache_dir / "jax"))
    return jax


def check_device(jax, chips: int, require_tpu: bool):
    from harness import peaks
    devices = jax.devices()
    d = devices[0]
    log(f"device: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devices)}")
    if require_tpu and d.platform != "tpu":
        raise NoDevice(f"bench: no TPU (JAX found {d.platform!r})")
    if len(devices) < chips:
        raise NoDevice(f"bench: the cell needs {chips} chips, found "
                       f"{len(devices)}")
    pk = peaks.peaks_for(d.device_kind) if require_tpu else None
    return devices, pk


def main(argv=None, *, root: Path = ROOT, src: Path | None = None,
         require_tpu: bool = True, compile_cache: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("fp8",), default=None)
    args = ap.parse_args(argv)

    root = Path(root)
    src = Path(src) if src is not None else root / "src"
    if not (src / "repro").is_dir():
        raise NoDevice(f"bench: the program (src/repro) is not under {root}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from harness import spec, warmup

    cell = spec.load_cell(root, args.workload)
    cache_dir = root / ".bench_cache"
    jax = setup_jax(cache_dir if compile_cache else None)
    devices, pk = check_device(jax, cell.chips, require_tpu)
    device = devices[0]
    counter = warmup.CompileCounter()
    try:
        return _run(args, root, cell, cache_dir, devices, pk, counter)
    finally:
        counter.close()


def _run(args, root, cell, cache_dir, devices, pk, counter) -> dict:
    from harness import check, measures, serve, spec, trace
    device = devices[0]

    trace_dir = None
    if args.trace:
        trace_dir = cache_dir / "trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    data = serve.run(cell, args.seed, args.seconds, t_start=T_START,
                     device=device, trace_dir=trace_dir,
                     trace_s=float(cell.mix.get("trace_s", 10.0)),
                     cache_dir=cache_dir, counter=counter, log=log)
    data.peaks = pk
    if trace_dir is not None:
        data.trace = trace.load(str(trace_dir),
                                data.trace_span[1] - data.trace_span[0])
        shutil.rmtree(trace_dir, ignore_errors=True)

    n_c, s_c, names = data.compiles
    late_med, late_max = serve.lateness(data.records)
    log(f"window {data.seconds:.3f}s after set-up {data.setup_s:.3f}s "
        f"(ramp {data.extra['ramp_s']}s); KV pages per engine "
        f"{data.extra['kv_pages']}; peak device memory "
        f"{data.extra['memory_peak_bytes']} B")
    log(f"compilations in the window: {n_c} ({s_c:.3f}s) {names}")
    if data.extra["compiles_in_trace"] is not None:
        n_t, s_t, _ = data.extra["compiles_in_trace"]
        log(f"compilations in the traced span: {n_t} ({s_t:.3f}s)")
    log(f"step shapes met outside the cell's list: "
        f"{data.extra['shapes_outside']} (recorded in this checkout: "
        f"{data.extra['shapes_recorded']})")
    log(f"generator lateness: median {late_med * 1e3:.3f} ms, "
        f"max {late_max * 1e3:.3f} ms")
    for kind in ("online", "offline"):
        log(f"{kind} admission (gateway.submit) in the window: "
            f"{serve.admission(data.records, kind, data.w0, data.w1)}")
    on = data.in_window("online")
    log(f"online due in window {len(on)}, ttft p50 "
        f"{measures.percentile(measures.ttfts(data), 50)}, tpot p50 "
        f"{measures.percentile(measures.tpots(data), 50)}, SLO attainment "
        f"{measures.slo_attainment(data)}; offline tokens/s "
        f"{measures.tokens_in_window(data, 'offline') / data.seconds}, "
        f"offline backlog at close {data.extra['offline_backlog']}")
    fifths = [data.w0 + k * data.seconds / 5 for k in range(6)]
    log("offline tokens/s by fifth of the window: " + str([
        round(measures.tokens_between(data, "offline", a, b) / (b - a), 3)
        for a, b in zip(fifths, fifths[1:])]))
    log(f"counters over the window: " + json.dumps(
        {k: _delta(data.counters0[k], data.counters1[k])
         for k in data.counters1}))

    # correctness: the program's state is freed; the reference runs now
    t = time.perf_counter()
    picked = check.sample(data, args.seed)
    limits = cell.config["limits"]
    # no finished request to compare reads as a gap far above any limit
    gap, ctl = (check.served_gap(cell.reference(), cell.model, args.seed,
                                 picked, quant=args.control)
                if picked else (1e9, 1e9))
    if args.control:
        log(f"control {args.control}: served tokens' gap {gap}, the "
            f"control's {ctl}; the control's is compared")
        gap = ctl
    checks = {
        "logit_gap": [gap, limits["logit_gap"]],
        "length_faults": [check.length_faults(data), 0],
    }
    log(f"reference over {len(picked)} requests "
        f"({sum(r.migrated for r in picked)} of them migrated; "
        f"{sum(r.migrated for r in data.records)} migrated in the run), "
        f"{sum(len(r.tokens) for r in picked)} served tokens: "
        f"{time.perf_counter() - t:.1f}s")
    correct = all(v <= lim for v, lim in checks.values())

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = spec.reader(root, m["name"])(data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted = len(on) + sum(
        1 for r in data.records if r.kind == "offline"
        and any(data.w0 <= t < data.w1 for t in r.times))
    failed = sum(1 for r in on if r.outcome in measures.FAILED)
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(devices),
           "memory_peak_bytes": data.extra["memory_peak_bytes"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if data.trace is not None and pk is not None:
        dev["busy_s"] = trace.busy_seconds(data.trace)
        dev["window_s"] = data.trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(data.trace),
                               "idle_gaps": trace.idle_gaps(data.trace)}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


def _delta(a, b):
    if isinstance(b, dict):
        return {k: b[k] - a.get(k, 0) for k in b}
    return b - a


if __name__ == "__main__":
    try:
        main()
    except NoDevice as e:
        print(e, file=sys.stderr)
        sys.exit(2)
