"""The program's spans in a trace, on synthetic events: idle gaps labelled
by the innermost program span, device time by step program, and the four
per-layer readers that read span stats."""

# benchtest first: it puts bench/ and src/ on the import path
from benchtest import BENCH
import importlib.util

import pytest

from harness import peaks, spans, trace
from harness.serve import RunData

MS = 1e6   # ns
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _launch(start, dur, kind, bucket, rows, row_steps, pages, live, k):
    return (HOST, "rt", "ooco.engine.launch", start, dur,
            {"kind": kind, "bucket": bucket, "rows": rows,
             "pages_padded": pages, "K": k, "row_steps": row_steps,
             "pages_live": live, "first_call": 0})


def _events():
    """Two gateway rounds over 20 ms; the device runs [0,3], [5,6], [8,9]
    and [12,19] ms."""
    ops = [(DEV, "XLA Ops", "%fusion.1 = bf16[8] fusion(%x)", a * MS,
            (b - a) * MS, {}) for a, b in ((0, 3), (5, 6), (8, 9), (12, 19))]
    mods = [(DEV, "XLA Modules", "jit_decode_horizon(3)", 0, 3 * MS, {}),
            (DEV, "XLA Modules", "jit_mixed_step(5)", 5 * MS, 1 * MS, {}),
            (DEV, "XLA Modules", "jit_decode_horizon(3)", 8 * MS, 1 * MS, {}),
            (DEV, "XLA Modules", "jit_mixed_horizon", 12 * MS, 7 * MS, {})]
    host = [
        # round 1 [0, 10): waits on the device 2 ms, gap [3,5] under args
        (HOST, "rt", "ooco.gateway.round", 0, 10 * MS, {}),
        (HOST, "rt", "ooco.rt.round", 0, 9.5 * MS, {}),
        (HOST, "rt", "ooco.rt.dispatch", 0, 4 * MS,
         {"kind": "decode_horizon", "predicted_s": 0.002}),
        (HOST, "rt", "ooco.engine.sync", 1 * MS, 2 * MS, {}),
        (HOST, "rt", "ooco.rt.dispatch", 4 * MS, 5 * MS,
         {"kind": "mixed_step", "predicted_s": 0.010}),
        (HOST, "rt", "ooco.engine.args", 4 * MS, 1.5 * MS, {}),
        _launch(5.5 * MS, 0.5 * MS, "mixed_step", 8, 6, 6, 4, 20, 1),
        # the gap [6,8] lies under the benchmark's span and the program's
        (HOST, "rt", "bench.relaxed.mixed_step", 4 * MS, 5 * MS, {}),
        (HOST, "rt", "ooco.engine.commit", 6 * MS, 2.5 * MS, {}),
        # round 2 [10, 20): waits 6 ms; gap [9,12] straddles the rounds
        (HOST, "rt", "ooco.gateway.round", 10 * MS, 10 * MS, {}),
        (HOST, "rt", "ooco.gateway.publish", 10 * MS, 2 * MS,
         {"streams": 600, "tokens": 128}),
        (HOST, "rt", "ooco.engine.sync", 13 * MS, 6 * MS, {}),
        # 8 rows, two of them finished after 3 of the 4 iterations
        _launch(12 * MS, 1 * MS, "mixed_horizon", 8, 8, 30, 16, 240, 4),
        _launch(12.5 * MS, 0.1 * MS, "mixed_step", 0, 0, 0, 0, 0, 1),
        # a span of another system is no program span
        (HOST, "rt", "oocox.other", 0, 20 * MS, {}),
    ]
    return ops + mods + host


def _trace():
    ev = _events()
    return spans.attach(trace.from_events([e[:5] for e in ev], 0.020), ev)


def test_attach_keeps_program_spans_with_stats():
    t = _trace()
    assert len(t.ops) == 4 and len(t.modules) == 4
    assert {s[0] for s in t.program} == {
        "ooco.gateway.round", "ooco.rt.round", "ooco.rt.dispatch",
        "ooco.engine.sync", "ooco.engine.args", "ooco.engine.launch",
        "ooco.engine.commit", "ooco.gateway.publish"}
    (pub,) = spans.spans(t, "ooco.gateway.publish")
    assert pub[3] == {"streams": 600, "tokens": 128}
    # what harness.trace reads of the same events is as before
    assert [h[0] for h in t.host] == ["bench.relaxed.mixed_step"]


def test_idle_gaps_program_and_bench_labels():
    t = _trace()
    # gaps [3,5], [6,8] and [9,12], each instant under the shortest program
    # span over it: [3,4] dispatch, [4,5] args, [6,8] commit, [9,9.5] the
    # runtime's round, [9.5,10] the gateway's, [10,12] publish
    assert dict(spans.idle_gaps_program(t)) == pytest.approx(
        {"ooco.rt.dispatch": 0.001, "ooco.engine.args": 0.001,
         "ooco.engine.commit": 0.002, "ooco.rt.round": 0.0005,
         "ooco.gateway.round": 0.0005, "ooco.gateway.publish": 0.002})
    # the benchmark's own reduction keeps its labels, by each gap's middle
    assert dict(trace.idle_gaps(t)) == pytest.approx(
        {"bench.relaxed.mixed_step": 0.004, "host: none": 0.003})


def test_idle_gaps_program_short_gaps_and_bare_time():
    ev = [(DEV, "XLA Ops", "%fusion.1 = f(%x)", a * MS, (b - a) * MS, {})
          for a, b in ((0, 1), (1.01, 2), (5, 6))]
    ev.append((HOST, "rt", "ooco.engine.args", 3 * MS, 1 * MS, {}))
    t = spans.attach(trace.from_events([e[:5] for e in ev], 0.006), ev)
    # [1, 1.01] is 10 us; [2,5] has args over [3,4] and nothing else
    assert dict(spans.idle_gaps_program(t)) == pytest.approx(
        {"gaps under 20 us": 1e-5, "ooco.engine.args": 0.001,
         "host: none": 0.002})


def test_device_programs_by_name():
    assert dict(spans.device_programs(_trace())) == pytest.approx(
        {"decode_horizon": 0.004, "mixed_horizon": 0.007,
         "mixed_step": 0.001})
    assert spans.program_name(
        "jit_decode_horizon(5325426750545320610)") == "decode_horizon"
    assert spans.program_name("decode_step") == "decode_step"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(tr):
    return RunData(None, 0, {}, 1.0, 0.0, 0.02, [], {}, {}, (0, 0, {}),
                   trace=tr, trace_span=(0.0, 0.020),
                   peaks=peaks.PEAKS["TPU v5 lite"])


READERS = ("host_round_ms", "decode_row_occupancy", "decode_kv_occupancy",
           "perf_model_error")


def test_readers_on_span_stats():
    d = _run(_trace())
    # rounds: 10 - 2 ms and 10 - 6 ms of sync -> median of 8 and 4
    assert _reader("host_round_ms")(d) == pytest.approx(6.0)
    # decode launches only: 6 of 8 rows once, 30 of 8 x 4 row-iterations
    assert _reader("decode_row_occupancy")(d) == pytest.approx(
        100 * (6 + 30) / (8 + 32))
    assert _reader("decode_kv_occupancy")(d) == pytest.approx(
        100 * (20 + 240) / (8 * 4 + 8 * 16 * 4))
    # predicted 2 ms against 4 ms (50%), 10 ms against 5 ms (100%)
    assert _reader("perf_model_error")(d) == pytest.approx(75.0)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_program_spans(name):
    # the parent's program opens no span: a trace without them, or none
    plain = trace.from_events([e[:5] for e in _events()], 0.020)
    assert _reader(name)(_run(plain)) is None
    assert _reader(name)(_run(spans.attach(plain, []))) is None
    assert _reader(name)(_run(None)) is None
    d = _run(_trace())
    d.peaks = None          # a CPU run: no device number
    assert _reader(name)(d) is None
    assert dict(spans.idle_gaps_program(plain)) == pytest.approx(
        {"host: none": 0.007})
    assert spans.device_programs(plain) == []


def test_load_reads_the_programs_spans_from_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.core.tracing import span

    double = jax.jit(lambda x: 2 * x)
    double(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("ooco.gateway.round"):
            with span("ooco.engine.launch", kind="decode_step", rows=3):
                double(jnp.ones(4)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    t = spans.load(str(tmp_path), 0.1)
    assert sorted(s[0] for s in t.program) == ["ooco.engine.launch",
                                               "ooco.gateway.round"]
    (launch,) = spans.spans(t, "ooco.engine.launch")
    assert launch[3] == {"kind": "decode_step", "rows": 3}
    assert t.modules == []        # the CPU trace has no device plane
