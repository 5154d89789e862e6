"""Arithmetic of the end-to-end metrics, over the client records of a window.

Tails are taken over every request the window owes: a request due in the
window with no first token by its end counts at its wait so far, and one
that failed or was refused counts as the longest value.
"""
from __future__ import annotations

import numpy as np

FAILED = ("refused", "error", "cancelled", "deadline")


def percentile(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


def ttfts(data, kind: str = "online") -> list[float]:
    vals, failed = [], 0
    for r in data.in_window(kind):
        if r.outcome in FAILED and not r.times:
            failed += 1
        elif r.times and r.times[0] < data.w1:
            vals.append(r.times[0] - r.due)
        else:
            vals.append(data.w1 - r.due)
    if failed:
        worst = max(vals + [data.w1 - data.w0])
        vals += [worst] * failed
    return vals


def tpots(data, kind: str = "online") -> list[float]:
    """Client-seen time per output token of each request due in the window
    with at least two tokens received inside it."""
    out = []
    for r in data.in_window(kind):
        ts = [t for t in r.times if t < data.w1]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1))
    return out


def tokens_in_window(data, kind: str) -> int:
    return tokens_between(data, kind, data.w0, data.w1)


def tokens_between(data, kind: str, t0: float, t1: float) -> int:
    return sum(1 for r in data.records if r.kind == kind
               for t in r.times if t0 <= t < t1)


def slo_attainment(data) -> float | None:
    slo = data.cell.mix.get("slo")
    rs = data.in_window("online")
    if not slo or not rs:
        return None
    ok = 0
    for r in rs:
        ts = [t for t in r.times if t < data.w1]
        if not ts or ts[0] - r.due > slo["ttft_s"]:
            continue
        if len(ts) >= 2 and (ts[-1] - ts[0]) / (len(ts) - 1) > slo["tpot_s"]:
            continue
        ok += 1
    return ok / len(rs)


def decode_contexts(data, t0: float, t1: float) -> list[int]:
    """For each decoded token received in [t0, t1): the keys its step
    attended over (prompt plus earlier tokens). The first token of a
    request comes from its prefill and is not a decode token."""
    out = []
    for r in data.records:
        p = len(r.prompt)
        for i, t in enumerate(r.times):
            if i and t0 <= t < t1:
                out.append(p + i)
    return out


def logit_rows(data, t0: float, t1: float) -> int:
    return sum(1 for r in data.records for t in r.times if t0 <= t < t1)
