"""One general traffic generator, driven by a mix file (``traffic/<mix>.json``).

Every seed gets the same multiset of sizes and of arrival gaps, in another
order: lengths are the quantiles ``(i + 0.5) / n`` of a lognormal with the
mix's mean and coefficient of variation (the arithmetic of
``repro.data.traces._lognormal_lengths``, copied so that the yardstick cannot
move), and gaps are the same quantiles of an exponential. The seed only
shuffles them and draws the prompt token ids, so the work of a run does not
change with its seed and seeds differ only in how that work interleaves.
The offline batch job's lengths keep one order for every seed (``spread``):
the runtime takes offline work first in, first out and a window sees only
the head of the batch, so that order is the window's work; it gives every
head of the batch its share of each part of the length distribution.

A mix file::

    {"pools": {"strict": 1, "relaxed": 1},
     "slo": {"ttft_s": 2.0, "tpot_s": 0.05},
     "ramp_s": 20,
     "online": {"rate_per_s": 1.6, "prompt_mean": 1512.3, "output_mean": 98.75,
                "cov": 1.0, "prompt_cap": 8192, "output_cap": 4096,
                "bursts": [{"at_frac": 0.4, "seconds": 5, "mult": 3.0}]},
     "offline": {"count": 600, "prompt_mean": 1200.52, "output_mean": 671.51,
                 "cov": 1.0, "prompt_cap": 8192, "output_cap": 4096}}

Times are seconds from the start of the ramp; the window is
``[ramp_s, ramp_s + seconds)``. Burst offsets are fractions of the window.
Offline requests are one batch job, all due at time 0 and submitted at
once (``harness.serve``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

#: shortest prompt or output drawn (as in repro.data.traces)
MIN_LEN = 4


@dataclass(frozen=True)
class Req:
    kind: str          # "online" | "offline"
    due: float         # seconds from the start of the ramp
    prompt: tuple[int, ...]
    output_len: int


def lognormal_quantiles(mean: float, cov: float, n: int, lo: int,
                        hi: int) -> np.ndarray:
    """The n mid-quantiles of a lognormal with this mean and CoV, clipped
    to [lo, hi] and rounded down to whole tokens."""
    sigma2 = math.log(1.0 + cov ** 2)
    mu = math.log(mean) - sigma2 / 2
    u = (np.arange(n) + 0.5) / n
    z = np.sqrt(2.0) * _erfinv(2.0 * u - 1.0)
    x = np.exp(mu + math.sqrt(sigma2) * z)
    return np.clip(x, lo, hi).astype(int)


def _erfinv(y: np.ndarray) -> np.ndarray:
    # scipy-free inverse error function: Giles' approximation refined by
    # two Newton steps on math.erf (exact to float64 rounding)
    y = np.asarray(y, np.float64)
    w = -np.log((1.0 - y) * (1.0 + y))
    small = w < 5.0
    ws = w - 2.5
    wl = np.sqrt(np.maximum(w, 5.0)) - 3.0
    ps = 2.81022636e-08
    for c in (3.43273939e-07, -3.5233877e-06, -4.39150654e-06, 0.00021858087,
              -0.00125372503, -0.00417768164, 0.246640727, 1.50140941):
        ps = c + ps * ws
    pl = -0.000200214257
    for c in (0.000100950558, 0.00134934322, -0.00367342844, 0.00573950773,
              -0.0076224613, 0.00943887047, 1.00167406, 2.83297682):
        pl = c + pl * wl
    x = np.where(small, ps, pl) * y
    erf = np.vectorize(math.erf)
    for _ in range(2):
        x = x - (erf(x) - y) / (2.0 / math.sqrt(math.pi) * np.exp(-x * x))
    return x


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """The n mid-quantiles of an exponential with this rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


#: irrational steps of the two-dimensional Kronecker sequence that orders
#: the offline batch (prompt lengths, output lengths)
STEPS = ((math.sqrt(5.0) - 1.0) / 2.0, math.sqrt(2.0) - 1.0)


def spread(n: int, step: float) -> np.ndarray:
    """A permutation of range(n) whose every head takes its share of each
    part of the range: the ranks of frac(j * step), j < n."""
    u = (np.arange(n) * step) % 1.0
    return np.argsort(np.argsort(u, kind="stable"), kind="stable")


def _lengths(spec: dict, n: int, rng: np.random.Generator,
             spread_heads: bool = False):
    cov = spec.get("cov", 1.0)
    p = lognormal_quantiles(spec["prompt_mean"], cov, n, MIN_LEN,
                            spec["prompt_cap"])
    o = lognormal_quantiles(spec["output_mean"], cov, n, MIN_LEN,
                            spec["output_cap"])
    if spread_heads:
        return p[spread(n, STEPS[0])], o[spread(n, STEPS[1])]
    return rng.permutation(p), rng.permutation(o)


def _arrivals(rate: float, start: float, end: float,
              rng: np.random.Generator) -> np.ndarray:
    """Open-loop arrivals at ``rate`` over [start, end): round(rate * span)
    exponential-quantile gaps in seeded order, so the count and the last
    arrival are the same for every seed."""
    n = int(round(rate * (end - start)))
    if n <= 0:
        return np.zeros(0)
    gaps = rng.permutation(exponential_gaps(rate, n))
    # the quantile gaps sum to about n / rate; scaling them onto the span
    # keeps every arrival inside it with the first one at ``start``
    return start + (end - start) * (np.cumsum(gaps) - gaps) / gaps.sum()


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Req]:
    """Every request of one run, sorted by due time."""
    rng = np.random.default_rng(seed)
    ramp = float(mix.get("ramp_s", 0.0))
    end = ramp + seconds
    out: list[Req] = []
    on = mix.get("online")
    if on:
        due = [_arrivals(on["rate_per_s"], 0.0, end, rng)]
        for b in on.get("bursts", []):
            b0 = ramp + b["at_frac"] * seconds
            extra = (b["mult"] - 1.0) * on["rate_per_s"]
            due.append(_arrivals(extra, b0, b0 + b["seconds"], rng))
        due = np.sort(np.concatenate(due))
        p, o = _lengths(on, len(due), rng)
        for t, pl, ol in zip(due, p, o):
            out.append(Req("online", float(t), _tokens(rng, pl, vocab),
                           int(ol)))
    off = mix.get("offline")
    if off:
        p, o = _lengths(off, off["count"], rng, spread_heads=True)
        for pl, ol in zip(p, o):
            out.append(Req("offline", 0.0, _tokens(rng, pl, vocab), int(ol)))
    out.sort(key=lambda r: (r.due, r.kind))
    return out


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> tuple[int, ...]:
    return tuple(int(x) for x in rng.integers(0, vocab, int(n)))
