"""Trace-to-metrics reduction on synthetic events: busy and idle share,
kernel time, roofline share and step MFU."""

# benchtest first: it puts bench/ and src/ on the import path
from benchtest import BENCH
import importlib.util

import pytest

from harness import peaks, trace
from harness.serve import Record, RunData

MS = 1e6   # ns


def _events():
    dev = "/device:TPU:0"
    ev = [
        (dev, "XLA Ops", "%fusion.1 = bf16[8] fusion(%x)", 0 * MS, 2 * MS),
        (dev, "XLA Ops", "%paged_attention.3 = bf16[8] custom-call(%y)",
         1 * MS, 2 * MS),                                       # overlaps
        (dev, "XLA Ops", "%fusion.2 = bf16[8] fusion(%paged_attention.3)",
         5 * MS, 1 * MS),
        (dev, "XLA Ops", "%paged_attention.3 = bf16[8] custom-call(%z)",
         8 * MS, 1 * MS),
        (dev, "XLA Ops", "%while.7 = (s32[]) while(%t)", 0 * MS, 9 * MS),
        (dev, "XLA Modules", "jit_step", 0 * MS, 9 * MS),   # not an op line
        ("/host:CPU", "python", "bench.rt_step", 0 * MS, 10 * MS),
        ("/host:CPU", "python", "bench.migrate", 3 * MS, 1.5 * MS),
        ("/host:CPU", "python", "other", 0 * MS, 10 * MS),
    ]
    return ev


def test_busy_idle_and_gaps():
    t = trace.from_events([e for e in _events() if "while" not in e[2]],
                          window_s=0.010)
    assert len(t.ops) == 4 and t.devices == 1
    # union: [0,3] + [5,6] + [8,9] = 5 ms
    assert trace.busy_seconds(t) == pytest.approx(0.005)
    gaps = dict(trace.idle_gaps(t))
    # [3,5] has its middle (4 ms) inside migrate; [6,8] inside rt_step only
    assert gaps == pytest.approx({"bench.migrate": 0.002,
                                  "bench.rt_step": 0.002})
    top = dict(trace.top_ops(t))
    assert top == pytest.approx({"paged_attention": 0.003, "fusion": 0.003})
    assert t.kernel_seconds("paged_attention") == pytest.approx(0.003)
    assert trace.op_name("%copy-start.1 = (s32[8]) copy-start(%a)") == \
        "copy-start"


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


M = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
     "head_dim": 16, "d_ff": 128, "vocab_size": 100, "dtype": "bfloat16"}


def _run(tr):
    # one request: prompt of 10, tokens at 1 ms (from prefill), 2 ms, 3 ms
    rec = Record("offline", 0.0, tuple(range(10)), 3, times=[0.001, 0.002,
                 0.003], tokens=[1, 2, 3], outcome="finished")
    d = RunData(None, 0, M, 1.0, 0.0, 0.01, [rec], {}, {}, (0, 0, {}),
                trace=tr, trace_span=(0.0, 0.010), prefill_spans=[(0, 10)],
                peaks=peaks.PEAKS["TPU v5 lite"])
    return d


def test_roofline_and_mfu_arithmetic():
    t = trace.from_events([e for e in _events() if "while" not in e[2]],
                          window_s=0.010)
    d = _run(t)
    # decode tokens 2 and 3 attend over 11 and 12 keys
    need = peaks.decode_attention_bytes(M, [11, 12])
    kv = 2 * 2 * 16 * 2 * 2        # K and V, Hkv, hd, bf16, layers
    qo = 2 * 4 * 16 * 2 * 2
    assert need == kv * 23 + qo * 2
    share = _reader("paged_decode_roofline")(d)
    assert share == pytest.approx(100 * need / 819e9 / 0.003)
    flops = peaks.forward_flops(M, [(0, 10)], [11, 12], 3)
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    attn_keys = sum(range(1, 11)) + 11 + 12
    assert flops == 2 * 2 * per_layer * 12 + 4 * 2 * 4 * 16 * attn_keys \
        + 2 * 64 * 100 * 3
    assert _reader("step_mfu")(d) == pytest.approx(
        100 * flops / (0.010 * 197e12))
    assert _reader("device_idle_share.offline")(d) == pytest.approx(50.0)


def test_no_trace_or_no_peaks_reads_nothing():
    d = _run(None)
    for name in ("paged_decode_roofline", "step_mfu",
                 "device_idle_share.offline"):
        assert _reader(name)(d) is None
    d = _run(trace.from_events(_events(), 0.01))
    d.peaks = None          # a CPU run: no device number
    for name in ("paged_decode_roofline", "step_mfu",
                 "device_idle_share.offline"):
        assert _reader(name)(d) is None


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
