"""Program spans and counters on the profiler's clock.

A tiny ``Gateway`` over a wall-clock ``PoolRuntime`` serves a few online and
offline requests under ``jax.profiler`` (chunked prefill, then a second
runtime on the legacy layer-granular prefill), and the ``.xplane.pb`` is read
back with ``ProfileData``:

* every name in ``repro.core.tracing.SPANS`` appears, and the program opens
  no span outside that list;
* engine spans nest inside ``ooco.rt.dispatch``, which nests inside
  ``ooco.rt.round``, inside ``ooco.gateway.round``;
* ``ooco.engine.launch`` stats are in range and ``ooco.rt.dispatch`` carries
  the perf model's prediction;
* each dispatch kind runs in an XLA module of its own name;
* the ``EngineStats`` occupancy counters equal the sums over the spans.
"""
import asyncio
import glob
import re
from pathlib import Path

import jax
import pytest

from repro.cluster.gateway import Gateway
from repro.cluster.runtime import PoolRuntime, WallClock
from repro.configs import get_config
from repro.core.request import Kind
from repro.core.tracing import SPANS
from repro.models.model import build_model

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
KINDS = ("decode_step", "decode_horizon", "mixed_step", "mixed_horizon",
         "prefill_layer")
COUNTERS = ("rows_dispatched", "rows_padded", "kv_pages_live",
            "kv_pages_fetched")


def _runtime(cfg, model, params, donor, chunk_tokens):
    return PoolRuntime(cfg, policy="ooco", n_strict=1, n_relaxed=1,
                       clock=WallClock(), backend="ref", num_pages=256,
                       page_size=8, slo_ttft=1.0, slo_tpot=0.03,
                       model=model, params=params, chunk_tokens=chunk_tokens,
                       decode_horizon=4, kernels_from=donor)


async def _serve(rt):
    gw = Gateway(rt)
    await gw.start()

    async def client(i, kind):
        stream = await gw.submit([i + 1] * 40, kind=kind, max_new_tokens=10)
        return [t async for t in stream], stream.outcome

    runs = [client(i, Kind.OFFLINE) for i in range(4)]
    runs.append(client(9, Kind.ONLINE))
    out = await asyncio.wait_for(asyncio.gather(*runs), timeout=240)
    await gw.drain(timeout=60)
    return out


def _events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out.append(((plane.name, i), e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The events of one traced serve and the runtimes that produced them."""
    cfg = get_config("qwen2.5-7b").reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    trace_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        chunked = _runtime(cfg, model, params, None, 16)
        served = asyncio.run(_serve(chunked))
        legacy = _runtime(cfg, model, params, chunked.kernel_donor, 0)
        served += asyncio.run(_serve(legacy))
    finally:
        jax.profiler.stop_trace()
    assert all(o == "finished" and len(t) == 10 for t, o in served)
    return _events(trace_dir), (chunked, legacy)


def _spans(events, name):
    return [e for e in events if e[1] == name]


def _inside(inner, outers):
    """Whether a span of ``outers`` on the same thread contains ``inner``."""
    return any(o[0] == inner[0] and o[2] <= inner[2] and inner[3] <= o[3]
               for o in outers)


def test_every_span_appears_and_no_other(traced):
    events, _ = traced
    names = {e[1] for e in events if e[1].startswith("ooco.")}
    assert names == set(SPANS)
    # every literal name the program opens is listed
    opened = set()
    for f in SRC.rglob("*.py"):
        opened |= set(re.findall(r'span\(\s*"(ooco\.[a-z_.]+)"',
                                 f.read_text()))
    assert opened <= set(SPANS)


def test_spans_nest_by_layer(traced):
    events, _ = traced
    dispatch = _spans(events, "ooco.rt.dispatch")
    rounds = _spans(events, "ooco.rt.round")
    gw_rounds = _spans(events, "ooco.gateway.round")
    for name in ("ooco.engine.args", "ooco.engine.launch",
                 "ooco.engine.sync", "ooco.engine.commit"):
        spans = _spans(events, name)
        assert spans and all(_inside(s, dispatch) for s in spans), name
    for name in ("ooco.rt.checks", "ooco.rt.admit", "ooco.rt.plan",
                 "ooco.rt.dispatch", "ooco.rt.pull", "ooco.rt.migrate"):
        assert all(_inside(s, rounds) for s in _spans(events, name)), name
    assert all(_inside(s, gw_rounds) for s in rounds)
    assert all(_inside(s, gw_rounds)
               for s in _spans(events, "ooco.gateway.publish"))


def test_launch_and_dispatch_stats(traced):
    events, _ = traced
    launches = _spans(events, "ooco.engine.launch")
    assert {s[4]["kind"] for s in launches} == set(KINDS)
    for _, _, _, _, st in launches:
        assert 0 <= st["rows"] <= st["bucket"]
        assert st["K"] >= 1 and st["first_call"] in (0, 1)
        assert st["rows"] <= st["row_steps"] <= st["rows"] * st["K"]
        if st["rows"]:
            assert 0 < st["pages_live"] <= (st["bucket"] * st["pages_padded"]
                                            * st["K"])
        else:
            assert st["bucket"] == st["pages_live"] == 0
    # nothing ran before the trace: each kind's first launch compiles
    for kind in KINDS:
        first = min((s for s in launches if s[4]["kind"] == kind),
                    key=lambda s: s[2])
        assert first[4]["first_call"] == 1, kind
    assert any(s[4]["first_call"] == 0 for s in launches)
    dispatches = _spans(events, "ooco.rt.dispatch")
    assert {s[4]["kind"] for s in dispatches} == set(KINDS)
    for d in dispatches:
        st = d[4]
        assert st["predicted_s"] > 0 and st["chunk"] >= 0
        assert st["role"] in ("strict", "relaxed") and st["K"] >= 1
        inner = [x[4] for x in launches if _inside(x, [d])]
        assert inner and sum(x["rows"] for x in inner) == st["rows"]


def test_round_gateway_and_runtime_stats(traced):
    events, runtimes = traced
    # every round of both runtimes was traced
    assert len(_spans(events, "ooco.rt.round")) == sum(
        rt.metrics.rounds for rt in runtimes)
    published = [s[4] for s in _spans(events, "ooco.gateway.publish")]
    assert sum(p["tokens"] for p in published) == 2 * 5 * 10
    assert max(p["streams"] for p in published) <= 5
    assert any(p["streams"] for p in published)
    def roles(name):
        return {s[4]["role"] for s in _spans(events, name)}
    assert roles("ooco.rt.plan") == {"strict", "relaxed"}
    # prefill runs on the relaxed pool; the strict pool pulls from it
    assert roles("ooco.rt.admit") == {"relaxed"}
    assert roles("ooco.rt.pull") == {"strict"}
    assert all(s[4]["tokens"] > 0 for s in _spans(events, "ooco.rt.migrate"))


def test_programs_named_by_kind(traced):
    events, _ = traced
    modules = {e[4].get("hlo_module") for e in events}
    assert {f"jit_{k}" for k in KINDS} <= modules


def test_counters_equal_span_sums(traced):
    events, runtimes = traced
    launches = [s[4] for s in _spans(events, "ooco.engine.launch")]
    sums = {
        "rows_dispatched": sum(s["row_steps"] for s in launches),
        "rows_padded": sum(s["bucket"] * s["K"] for s in launches),
        "kv_pages_live": sum(s["pages_live"] for s in launches),
        "kv_pages_fetched": sum(s["bucket"] * s["pages_padded"] * s["K"]
                                for s in launches),
    }
    summaries = [rt.summary() for rt in runtimes]
    for k in COUNTERS:
        assert sum(s[k] for s in summaries) == sums[k] > 0, k
    assert sums["rows_dispatched"] <= sums["rows_padded"]
    assert sums["kv_pages_live"] <= sums["kv_pages_fetched"]


def test_pages_read_sums_each_iterations_pages():
    from repro.engine.engine import pages_read
    start = [0, 7, 8, 30, 5]
    steps = [1, 3, 16, 2, 0]
    brute = sum(p // 8 + 1 for s, n in zip(start, steps)
                for p in range(s, s + n))
    assert pages_read(start, steps, 8) == brute == 1 + 5 + 40 + 8


def test_masked_horizon_rows_count_as_padding():
    """A row that finishes mid-horizon counts only the iterations it ran,
    and only the pages its positions cover at each of them."""
    import numpy as np
    from repro.core.request import Request
    from repro.engine.engine import ServingEngine
    cfg = get_config("qwen2.5-7b").reduced()
    model = build_model(cfg, remat=False)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)),
                        num_pages=64, page_size=8, decode_buckets=(4,),
                        backend="ref")
    rids = []
    for n_out in (3, 9):    # 1 token from prefill, then 2 and 8 to decode
        req = Request(Kind.OFFLINE, 0.0, 12, n_out)
        eng.add_request(req, list(range(1, 13)))
        eng.prefill(req.rid)
        rids.append(req.rid)
    before = {k: getattr(eng.stats, k) for k in COUNTERS}
    eng.decode_horizon(rids, 8)
    got = {k: getattr(eng.stats, k) - before[k] for k in COUNTERS}
    # both rows start at position 12 (2 pages of 8; from 16 on, 3); the
    # first runs 2 iterations, the second 8
    assert got["rows_dispatched"] == 2 + 8
    assert got["rows_padded"] == 4 * 8
    assert got["kv_pages_live"] == 2 * 2 + (4 * 2 + 4 * 3)
    assert got["kv_pages_fetched"] == 4 * 4 * 8   # tables padded to 4 pages
    assert all(eng.requests[r].done for r in rids)
