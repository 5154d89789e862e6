"""Reduction of the program's own spans and step programs in a profiler
trace.

The program opens ``jax.profiler.TraceAnnotation`` s named ``ooco.*``
(``repro.core.tracing.SPANS``) with stats; they share the device trace's
clock. Events are read as ``(plane, line, name, start_ns, dur_ns, stats)``
so the arithmetic can be checked on synthetic events. ``attach`` adds to a
``harness.trace.Trace``:

* ``program``: the ``ooco.`` host spans, ``(name, start_ns, dur_ns, stats)``;
* ``modules``: the device's step programs, ``(name, start_ns, dur_ns)``, from
  the ``XLA Modules`` line of each ``/device:`` plane.

The per-layer readers ``host_round_ms``, ``decode_row_occupancy``,
``decode_kv_occupancy`` and ``perf_model_error`` read ``Trace.program`` and
read nothing where a trace has no such list, or no such span.

``bench/run.py`` does not call this module yet. Once it does, ``load``,
``read_events`` and ``attach`` belong in ``harness.trace`` (one loader),
``idle_gaps_program`` replaces ``harness.trace.idle_gaps``, and the
benchmark's own ``bench.*`` spans (``harness.serve``) can go.
"""
from __future__ import annotations

import bisect
import glob
import re
import statistics
from collections import defaultdict

from harness import trace

PREFIX = "ooco."
MODULE_LINE = "XLA Modules"


def read_events(path: str):
    """The trace's events; stats are read for host events only (the
    device's operations are many and none of their stats is read)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            for e in line.events:
                yield (plane.name, line.name, e.name, e.start_ns,
                       e.duration_ns, dict(e.stats) if host else {})


def attach(t: trace.Trace, events) -> trace.Trace:
    """Adds the program's spans and the device's step programs to ``t``."""
    t.program, t.modules = [], []
    for plane, line, name, start, dur, stats in events:
        if plane.startswith("/host:") and name.startswith(PREFIX):
            t.program.append((name, float(start), float(dur), stats))
        elif plane.startswith("/device:") and line == MODULE_LINE:
            t.modules.append((name, float(start), float(dur)))
    return t


def load(trace_dir: str, window_s: float) -> trace.Trace:
    """``harness.trace.load`` with the program's spans and step programs."""
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = list(read_events(paths[-1]))
    t = trace.from_events([e[:5] for e in events], window_s)
    return attach(t, events)


def spans(t, name: str) -> list:
    """The program's spans named ``name`` (empty where the trace has none)."""
    return [s for s in getattr(t, "program", None) or () if s[0] == name]


def program_name(module: str) -> str:
    """A step program's own name from its module's, as a v5e trace names it
    (``jit_decode_horizon(5325426750545320610)`` -> ``decode_horizon``)."""
    name = re.sub(r"\(.*\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def device_programs(t, n: int = 10) -> list:
    """Device time by step program, summed over the chips' module events."""
    tot = defaultdict(float)
    for name, _, d in getattr(t, "modules", None) or ():
        tot[program_name(name)] += d * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]


def idle_gaps_program(t, n: int = 10, short_ns: float = 20e3) -> list:
    """Idle device time between operations, split among the program spans
    over it: each idle instant goes to the innermost (shortest) span that
    covers it, ``host: none`` where none does. A gap often spans several
    host phases (commit, publish, admission, planning, launch), so it is
    split rather than labelled whole by its middle as
    ``harness.trace.idle_gaps`` labels it. Gaps under ``short_ns`` are
    summed under one label of their own."""
    merged = trace._merge((s, s + d) for _, s, d in t.ops)
    prog = sorted((s[:3] for s in getattr(t, "program", None) or ()),
                  key=lambda h: h[1])
    starts = [h[1] for h in prog]
    longest = max((h[2] for h in prog), default=0.0)
    tot = defaultdict(float)
    for (_, a), (b, _) in zip(merged, merged[1:]):
        if b - a < short_ns:
            tot["gaps under 20 us"] += (b - a) * 1e-9
            continue
        lo = bisect.bisect_left(starts, a - longest)
        hi = bisect.bisect_left(starts, b)
        over = [h for h in prog[lo:hi] if h[1] + h[2] > a]
        cuts = sorted({a, b} | {x for h in over for x in (h[1], h[1] + h[2])
                                if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            cover = [h for h in over if h[1] <= x and y <= h[1] + h[2]]
            label = min(cover, key=lambda h: h[2])[0] if cover \
                else "host: none"
            tot[label] += (y - x) * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]


def host_round_ms(t):
    """Median over the gateway's rounds of the round's time less the time
    its engines waited for the device (``ooco.engine.sync``), in ms."""
    rounds = spans(t, "ooco.gateway.round")
    if not rounds:
        return None
    syncs = sorted(spans(t, "ooco.engine.sync"), key=lambda s: s[1])
    starts = [s[1] for s in syncs]
    own = []
    for _, a, d, _ in rounds:
        lo = bisect.bisect_left(starts, a)
        hi = bisect.bisect_right(starts, a + d)
        waited = sum(s[2] for s in syncs[lo:hi] if s[1] + s[2] <= a + d)
        own.append((d - waited) * 1e-6)
    return statistics.median(own)


def _decode_launches(t) -> list:
    return [s[3] for s in spans(t, "ooco.engine.launch")
            if s[3].get("rows", 0) > 0]


def decode_row_occupancy(t):
    """The decode iterations real rows ran (``row_steps``; a row that
    finished mid-horizon rides masked and counts as padding) over the rows
    the padded steps ran (bucket x K), over the launches that carry decode
    rows; in %."""
    ls = _decode_launches(t)
    if not ls:
        return None
    rows = sum(s["row_steps"] for s in ls)
    padded = sum(s["bucket"] * s["K"] for s in ls)
    return 100.0 * rows / padded


def decode_kv_occupancy(t):
    """The KV pages the real row-iterations read (``pages_live``, the pages
    covering each one's positions) over the pages the padded decode steps
    fetch (bucket x padded page count x K); in %."""
    ls = _decode_launches(t)
    if not ls:
        return None
    live = sum(s["pages_live"] for s in ls)
    fetched = sum(s["bucket"] * s["pages_padded"] * s["K"] for s in ls)
    return 100.0 * live / fetched


def perf_model_error(t):
    """Median over the runtime's dispatches of |predicted / measured - 1|,
    the roofline model's estimate against the dispatch span; in %."""
    ds = [s for s in spans(t, "ooco.rt.dispatch") if s[2] > 0]
    if not ds:
        return None
    return statistics.median(
        100.0 * abs(s[3]["predicted_s"] / (s[2] * 1e-9) - 1.0) for s in ds)
