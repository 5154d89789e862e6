"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps labelled by what the host was doing.

The trace is read as flat event tuples ``(plane, line, name, start_ns,
dur_ns)`` so the arithmetic can be checked on synthetic events. Device
operations are the events of the ``XLA Ops`` line of each ``/device:``
plane; host spans are the benchmark's own ``TraceAnnotation`` s, whose
names start with ``bench.``.
"""
from __future__ import annotations

import bisect
import glob
from collections import defaultdict
from dataclasses import dataclass, field

HOST_PREFIX = "bench."
DEVICE_LINE = "XLA Ops"


def op_name(text: str) -> str:
    """An operation's own name from its HLO text (``%fusion.3 = ...`` ->
    ``fusion``): operands, shapes and the instance number dropped."""
    name = text.split(" = ", 1)[0].lstrip("%")
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


#: operations that contain others (a scan's loop) and would count twice
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Trace:
    window_s: float                          # host clock, start to stop
    ops: list = field(default_factory=list)  # (name, start_ns, dur_ns)
    host: list = field(default_factory=list)  # (name, start_ns, dur_ns)
    devices: int = 1

    def kernel_seconds(self, name: str) -> float:
        """Summed device time of the operations named ``name``."""
        return sum(d for n, _, d in self.ops if op_name(n) == name) * 1e-9


def read_events(path: str):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                yield plane.name, line.name, e.name, e.start_ns, e.duration_ns


def load(trace_dir: str, window_s: float) -> Trace:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_events(read_events(paths[-1]), window_s)


def from_events(events, window_s: float) -> Trace:
    t = Trace(window_s)
    planes = set()
    for plane, line, name, start, dur in events:
        if plane.startswith("/device:") and line == DEVICE_LINE:
            t.ops.append((name, float(start), float(dur)))
            planes.add(plane)
        elif plane.startswith("/host:") and name.startswith(HOST_PREFIX):
            t.host.append((name, float(start), float(dur)))
    t.devices = max(len(planes), 1)
    return t


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(t: Trace) -> float:
    """Union of device-operation intervals, averaged over the chips."""
    merged = _merge((s, s + d) for _, s, d in t.ops)
    return sum(e - s for s, e in merged) * 1e-9 / t.devices


def top_ops(t: Trace, n: int = 10) -> list:
    """Device time by operation name, loops that contain others left out."""
    tot = defaultdict(float)
    for name, _, d in t.ops:
        short = op_name(name)
        if short not in CONTAINERS:
            tot[short] += d * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]


def idle_gaps(t: Trace, n: int = 10, short_ns: float = 20e3) -> list:
    """Idle device time between operations, summed by the innermost host
    span that covers the middle of each gap (``host: none`` if none).
    Gaps under ``short_ns`` (launch gaps inside a program) are summed
    under one label of their own."""
    merged = _merge((s, s + d) for _, s, d in t.ops)
    spans = sorted(t.host, key=lambda h: h[1])
    starts = [h[1] for h in spans]
    longest = max((h[2] for h in spans), default=0.0)
    tot = defaultdict(float)
    for (_, a), (b, _) in zip(merged, merged[1:]):
        if b - a < short_ns:
            tot["gaps under 20 us"] += (b - a) * 1e-9
            continue
        mid = (a + b) / 2
        lo = bisect.bisect_left(starts, mid - longest)
        hi = bisect.bisect_right(starts, mid)
        cover = [h for h in spans[lo:hi] if mid <= h[1] + h[2]]
        label = min(cover, key=lambda h: h[2])[0] if cover else "host: none"
        tot[label] += (b - a) * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]
