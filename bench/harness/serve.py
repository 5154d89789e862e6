"""One run of a serving cell: set-up, ramp, window, and the records that the
metric readers and the correctness check read.

The system under test is ``repro.cluster.gateway.Gateway`` over a wall-clock
``PoolRuntime`` built with the serving CLI's defaults (``repro.launch.serve``:
policy ooco, chunk and horizon ``auto``, prefix cache on, decode bucket 8,
page 16). The cell sets only the configuration, the pool sizes, the KV pages
(what memory the weights leave) and the TTFT/TPOT limits. Open-loop asyncio
clients in this process submit on the seeded schedule and timestamp every
token they receive; TTFT runs from the time a request was due. The offline
batch job is submitted whole before the ramp (``_admit_batch``).
"""
from __future__ import annotations

import asyncio
import concurrent.futures
import contextlib
import gc
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np

from harness import traffic, warmup, weights

PAGE = 16
DECODE_BUCKET = 8
#: share of the device memory the weights leave that goes to KV pages; the
#: rest holds the step programs' temporaries and the migration buffers
KV_SHARE = 0.75


@dataclass
class Record:
    """What one client saw of one request."""
    kind: str
    due: float                       # host clock
    prompt: tuple
    output_len: int
    sent: float | None = None        # host clock when the client woke
    admitted: float | None = None    # host clock when submit returned
    times: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    outcome: str | None = None       # gateway outcome, or "refused"
    req: object = None               # the runtime's Request (counters only)
    migrated: bool = False           # its KV moved between engines


@dataclass
class RunData:
    """Everything a metric reader may read."""
    cell: object
    seed: int
    model: dict
    setup_s: float
    w0: float                        # window start, host clock
    w1: float                        # window end, host clock
    records: list
    counters0: dict
    counters1: dict
    compiles: tuple                  # (count, seconds, names) in the window
    trace: object = None             # harness.trace.Trace of the traced span
    trace_span: tuple | None = None  # (t0, t1) host clock
    prefill_spans: list = field(default_factory=list)
    peaks: dict | None = None
    extra: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.w1 - self.w0

    def in_window(self, kind: str) -> list:
        return [r for r in self.records
                if r.kind == kind and self.w0 <= r.due < self.w1]


def model_config(model: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(**model)


def kv_bytes_per_page(m: dict) -> int:
    from harness.peaks import ITEMSIZE
    return (2 * m["num_layers"] * PAGE * m["num_kv_heads"] * m["head_dim"]
            * ITEMSIZE[m["dtype"]])


def pages_for_chip(m: dict, spec: dict, engines: int, device) -> int:
    """KV pages per engine: ``KV_SHARE`` of the device memory that the weights
    leave, split evenly over the engines (the arithmetic of
    ``chip_smoke.pages_for_chip``). Worked out from the device's limit and
    the weights' bytes, not from live usage, so every run of a cell gets the
    same pool shape and finds its step programs in the compile cache."""
    from harness.peaks import ITEMSIZE
    weights_bytes = sum(int(np.prod(shape)) for shape, _, _ in spec.values()
                        ) * ITEMSIZE[m["dtype"]]
    free = device.memory_stats()["bytes_limit"] - weights_bytes
    return int(KV_SHARE * free) // (engines * kv_bytes_per_page(m))


def check_params(params, model) -> None:
    """The benchmark's weights must have the program's tree, shapes and
    dtypes exactly; a program that changed its layout stops here."""
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("seeded weights do not match the program's "
                         f"parameter tree:\n{want}\nvs\n{got}")


def build(cell, seed: int, device):
    """Weights on the device from the seed, then the runtime."""
    from repro.cluster.runtime import PoolRuntime, WallClock
    from repro.models.model import build_model

    m = cell.model
    cfg = model_config(m)
    model = build_model(cfg, remat=False)
    spec = cell.reference().param_spec(m)
    params = weights.make_params(spec, seed, m["num_layers"], cfg.jnp_dtype)
    check_params(params, model)
    pools = cell.mix["pools"]
    engines = pools["strict"] + pools["relaxed"]
    pages = pages_for_chip(m, spec, engines, device)
    slo = cell.mix["slo"]
    rt = PoolRuntime(cfg, policy="ooco", n_strict=pools["strict"],
                     n_relaxed=pools["relaxed"], clock=WallClock(),
                     slo_ttft=slo["ttft_s"], slo_tpot=slo["tpot_s"],
                     num_pages=pages, page_size=PAGE, seed=seed,
                     backend="auto", decode_buckets=(DECODE_BUCKET,),
                     chunk_tokens="auto", decode_horizon="auto",
                     prefix_cache=True, model=model, params=params)
    return rt, pages


def counters(rt) -> dict:
    """Runtime and engine counters, read without the gateway's lock (the
    runtime thread keeps that lock nearly all the time under load; a torn
    read of a few integers is off by at most one round)."""
    pools = rt.strict_pool + rt.relaxed_pool + rt.dead_pool
    kinds: dict[str, int] = {}
    for s in pools:
        for k, v in s.engine.stats.dispatches_by_kind.items():
            kinds[k] = kinds.get(k, 0) + v
    m = rt.metrics
    return {
        # as PoolRuntime.summary() counts them
        "preemptions": sum(s.engine.stats.preemptions
                           for s in rt.relaxed_pool + rt.dead_pool)
        + m.chunk_preemptions,
        "migrations": m.migrations, "pulls": m.pulls,
        "evictions": m.evictions, "chunks": m.chunks,
        "chunk_preemptions": m.chunk_preemptions, "rounds": m.rounds,
        "host_syncs": sum(s.engine.stats.host_syncs for s in pools),
        "horizon_steps": sum(s.engine.stats.horizon_steps for s in pools),
        "dispatches_by_kind": kinds,
        "engine_crashes": m.engine_crashes,
    }


def _annotate(rt):
    """Host spans around the calls the benchmark makes into the program:
    each runtime round, each engine dispatch and each KV migration."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, name, label):
        fn = getattr(obj, name)

        def traced(*a, **k):
            with TraceAnnotation(label):
                return fn(*a, **k)
        setattr(obj, name, traced)

    wrap(rt, "step", "bench.rt_step")
    wrap(rt, "_migrate", "bench.migrate")
    for slot in rt.strict_pool + rt.relaxed_pool:
        for kind in ("decode_step", "decode_horizon", "mixed_step",
                     "mixed_horizon"):
            wrap(slot.engine, kind, f"bench.{slot.role}.{kind}")


def _track_migrations(rt) -> set:
    """The ids of the requests whose KV the runtime moved between engines,
    so that the correctness sample can take one of them."""
    moved: set = set()
    fn = getattr(rt, "_migrate", None)
    if fn is None:
        return moved

    def migrate(req, *a, **k):
        out = fn(req, *a, **k)
        moved.add(req.rid)
        return out
    rt._migrate = migrate
    return moved


async def _submit(gw, req: traffic.Req, rec: Record, trace: bool):
    """One ``gateway.submit``; the stream, or None if it was refused."""
    from repro.cluster.gateway import AdmissionRejected, GatewayClosed
    from repro.core.request import Kind

    rec.sent = time.perf_counter()
    kind = Kind.ONLINE if req.kind == "online" else Kind.OFFLINE
    span = (jax.profiler.TraceAnnotation("bench.submit") if trace
            else contextlib.nullcontext())
    try:
        with span:
            stream = await gw.submit(list(req.prompt), kind=kind,
                                     max_new_tokens=req.output_len)
    except (AdmissionRejected, GatewayClosed):
        rec.outcome = "refused"
        return None
    rec.req = stream.req
    rec.admitted = time.perf_counter()
    return stream


async def _consume(stream, rec: Record):
    async for tok in stream:
        rec.times.append(time.perf_counter())
        rec.tokens.append(tok)
    rec.outcome = stream.outcome


async def _client(gw, req: traffic.Req, rec: Record, trace: bool):
    delay = rec.due - time.perf_counter()
    if delay > 0:
        await asyncio.sleep(delay)
    stream = await _submit(gw, req, rec, trace)
    if stream is not None:
        await _consume(stream, rec)


class _Inline(concurrent.futures.ThreadPoolExecutor):
    """An executor that runs each call at once in the calling thread."""

    def submit(self, fn, /, *args, **kwargs):
        f = concurrent.futures.Future()
        try:
            f.set_result(fn(*args, **kwargs))
        except BaseException as e:  # noqa: BLE001 — handed to the awaiter
            f.set_exception(e)
        return f


async def _admit_batch(gw, batch, traced: bool) -> list:
    """Submits the whole offline batch job at once, as one job is handed
    in, before the runtime has work. Its runtime thread retakes the
    gateway's lock as soon as a round ends, so a submit that waits for the
    lock while rounds run may wait out the window (PERF.md, section 6);
    the batch therefore goes in under one hold of that lock, each submit
    run in this thread. Returns one stream per request (None if refused)."""
    loop = asyncio.get_running_loop()
    loop.set_default_executor(_Inline(max_workers=1))
    lock = getattr(gw, "_lock", None) or contextlib.nullcontext()
    streams = []
    with lock:
        for q, r in batch:
            r.due = time.perf_counter()
            streams.append(await _submit(gw, q, r, traced))
    return streams


async def _drive(rt, reqs, ramp, seconds, trace_dir, trace_s):
    from repro.cluster.gateway import Gateway

    gw = Gateway(rt)
    await gw.start()
    traced = trace_dir is not None
    batch = [(q, Record(q.kind, 0.0, q.prompt, q.output_len))
             for q in reqs if q.kind == "offline"]
    streams = await _admit_batch(gw, batch, traced)
    # every online submit gets a thread of its own, so that none waits
    # behind another in the executor, only for the gateway
    online = [q for q in reqs if q.kind == "online"]
    asyncio.get_running_loop().set_default_executor(
        concurrent.futures.ThreadPoolExecutor(max_workers=len(online) + 4))
    t0 = time.perf_counter()
    records = [r for _, r in batch]
    tasks = [asyncio.create_task(_consume(s, r))
             for s, (_, r) in zip(streams, batch) if s is not None]
    for q in online:
        rec = Record(q.kind, t0 + q.due, q.prompt, q.output_len)
        records.append(rec)
        tasks.append(asyncio.create_task(_client(gw, q, rec, traced)))
    w0, w1 = t0 + ramp, t0 + ramp + seconds
    await asyncio.sleep(max(w0 - time.perf_counter(), 0.0))
    c0 = counters(rt)
    span, prog = None, None
    if trace_dir is not None:
        # a traced span in the window, over the burst (40% in)
        await asyncio.sleep(max(w0 + 0.3 * seconds - time.perf_counter(),
                                0.0))
        prog0 = _prefill_progress(records)
        # device ops and the benchmark's own host spans; no Python tracer,
        # which would slow every host call by tracing it
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        ts0 = time.perf_counter()
        await asyncio.sleep(max(min(ts0 + trace_s, w1) - time.perf_counter(),
                                0.0))
        prog1 = _prefill_progress(records)
        span = (ts0, time.perf_counter())
        jax.profiler.stop_trace()
        prog = (prog0, prog1)
    await asyncio.sleep(max(w1 - time.perf_counter(), 0.0))
    w1 = time.perf_counter()
    c1 = counters(rt)
    backlog = len(rt.offline_queue)
    crashed = gw.crashed
    await gw.stop()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    if crashed is not None:
        raise RuntimeError("the gateway's runtime thread failed") from crashed
    return records, w0, w1, c0, c1, backlog, span, prog


def _prefill_progress(records) -> dict:
    return {id(r): (r.req.prefill_tokens_done, r.req.recompute_tokens,
                    r.req.cached_tokens)
            for r in records if r.req is not None}


def run(cell, seed: int, seconds: float, *, t_start: float, device,
        trace_dir: Path | None, trace_s: float, cache_dir: Path,
        counter: warmup.CompileCounter, log, keep: dict | None = None
        ) -> RunData:
    """``keep`` carries the step programs from one run to the next in the
    same process (several seeds in one process, as calibration does)."""
    mix = cell.mix
    m = cell.model
    rt, pages = build(cell, seed, device)
    if keep is not None:
        if "fns" in keep:
            for slot in rt.strict_pool + rt.relaxed_pool:
                for attr, fns in keep["fns"].items():
                    setattr(slot.engine, attr, fns)
        keep["fns"] = {a: getattr(rt.kernel_donor, a)
                       for a in warmup.KINDS.values()}
    log(f"weights and runtime: {time.perf_counter() - t_start:.1f}s, "
        f"{pages} KV pages ({pages * PAGE} tokens) per engine")
    donor = rt.kernel_donor
    caps = [mix[k]["prompt_cap"] + mix[k]["output_cap"]
            for k in ("online", "offline") if k in mix]
    max_pages = donor.pad_pages(-(-max(caps) // PAGE))
    # only the committed list is warmed, so every run of a checkout does
    # the same set-up; what runs met beyond it is recorded beside the
    # compile cache and printed, for the next benchmark PR to commit
    listed = cell.root / "bench" / "shapes" / f"{cell.name}.json"
    met = cache_dir / "shapes" / f"{cell.name}.json"
    shapes = warmup.planned([listed], DECODE_BUCKET, max_pages)
    t = time.perf_counter()
    failed = warmup.warm(donor, shapes)
    log(f"warmed {len(shapes) - failed} step programs in "
        f"{time.perf_counter() - t:.1f}s ({failed} shapes the engine "
        f"cannot make)")
    if trace_dir is not None:
        _annotate(rt)
    moved = _track_migrations(rt)
    reqs = traffic.generate(mix, seed, seconds, m["vocab_size"])
    ramp = float(mix.get("ramp_s", 0.0))
    records, w0, w1, c0, c1, backlog, span, prog = asyncio.run(
        _drive(rt, reqs, ramp, seconds, trace_dir, trace_s))
    for r in records:
        r.migrated = r.req is not None and r.req.rid in moved
    new_shapes = warmup.outside(donor, listed)
    n_shapes = warmup.record(donor, met)
    compiles = counter.between(w0, w1)
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    data = RunData(cell, seed, m, w0 - t_start, w0, w1, records, c0, c1,
                   compiles, trace_span=span)
    data.extra.update(kv_pages=pages, offline_backlog=backlog,
                      memory_peak_bytes=peak, shapes_recorded=n_shapes,
                      shapes_outside=new_shapes, ramp_s=ramp,
                      compiles_in_trace=(counter.between(*span)
                                         if span else None))
    if prog is not None:
        data.prefill_spans = _spans(records, *prog)
    # free the program's state before the reference runs on the chip
    for r in records:
        r.req = None
    del rt, donor
    gc.collect()
    return data

def _spans(records, prog0, prog1) -> list:
    """Prompt positions computed while the trace ran: progress that moved
    forward with no recompute in between (cached prefixes never count)."""
    out = []
    for r in records:
        a, b = prog0.get(id(r)), prog1.get(id(r))
        if b is None:
            continue
        start = max(a[0] if a else 0, b[2])
        if (a is None or a[1] == b[1]) and b[0] > start:
            out.append((start, b[0]))
    return out


def admission(records, kind: str, t0: float, t1: float) -> dict:
    """How long ``gateway.submit`` took for requests due in [t0, t1), and
    how many were still not admitted at t1."""
    rs = [r for r in records if r.kind == kind and t0 <= r.due < t1]
    waits = [r.admitted - r.sent for r in rs
             if r.admitted is not None and r.admitted < t1]
    return {"due": len(rs), "admitted": len(waits),
            "p50_s": float(np.median(waits)) if waits else None,
            "max_s": float(max(waits)) if waits else None}


def lateness(records) -> tuple[float, float]:
    """How late the clients woke after their due time: median and max."""
    late = [r.sent - r.due for r in records if r.sent is not None]
    return (float(np.median(late)), float(max(late))) if late else (0.0, 0.0)
