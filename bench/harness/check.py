"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample drawn
from the seed of the requests that were served tokens (the one with the
most always among them, and one whose KV was migrated between engines
whenever there is one) is run once through the configuration's
plain float32 reference, over each prompt followed by its served tokens.
The number compared is the widest gap by which a served token's logit lies
below the reference's best logit at that position. Every request is greedy,
so a sound path serves the reference's best token up to rounding near ties.

Each number compared is printed beside its limit; the run is correct when
each is within its limit.
"""
from __future__ import annotations

import numpy as np

#: served tokens the sample aims for, and at most this many requests
SAMPLE_TOKENS = 400
SAMPLE_MAX = 8


def sample(data, seed: int) -> list:
    """Requests served tokens in the window, finished or not (a long one is
    still running at the close), each with every token it was served."""
    done = [r for r in data.records
            if any(data.w0 <= t < data.w1 for t in r.times)]
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    longest = max(done, key=lambda r: (len(r.tokens), len(r.prompt)))
    out = [longest]
    moved = [r for r in done if r.migrated and r is not longest]
    if moved and not longest.migrated:
        out.append(moved[rng.integers(len(moved))])
    rest = [r for r in done if all(r is not o for o in out)]
    order = rng.permutation(len(rest))
    n = sum(len(r.tokens) for r in out)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def gaps(ref_logits: list, served: list) -> np.ndarray:
    """Per served token: reference best logit minus the served token's."""
    out = []
    for lg, toks in zip(ref_logits, served):
        lg = np.asarray(lg, np.float64)
        out.append(lg.max(axis=1) - lg[np.arange(len(toks)), toks])
    return np.concatenate(out) if out else np.zeros(0)


def reference_inputs(picked: list):
    seqs = [list(r.prompt) + list(r.tokens[:-1]) for r in picked]
    rows = [list(range(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens)))
            for r in picked]
    return seqs, rows


def served_gap(reference, model: dict, seed: int, picked: list,
               quant: str | None = None):
    """(widest gap of the served tokens, widest gap of the tokens that the
    ``quant`` control would put first) under the float32 reference."""
    seqs, rows = reference_inputs(picked)
    ref = reference.logits(model, seed, seqs, rows)
    served = [list(r.tokens) for r in picked]
    g = gaps(ref, served)
    if quant is None:
        return float(g.max(initial=0.0)), None
    ctl = reference.logits(model, seed, seqs, rows, quant=quant)
    firsts = [list(np.argmax(c, axis=1)) for c in ctl]
    return float(g.max(initial=0.0)), float(gaps(ref, firsts).max(initial=0.0))


def length_faults(data) -> int:
    """Finished requests that did not get exactly their output length, or
    got a token outside the vocabulary."""
    V = data.model["vocab_size"]
    return sum(1 for r in data.records if r.outcome == "finished"
               and (len(r.tokens) != r.output_len
                    or any(not 0 <= t < V for t in r.tokens)))
