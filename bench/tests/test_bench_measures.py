"""Metric arithmetic over a synthetic window: tails and rates cover the
whole window, and a stall inserted into it moves them."""

# benchtest first: it puts bench/ and src/ on the import path
from benchtest import BENCH
import importlib.util

import pytest

from harness import check, measures
from harness.serve import Record, RunData


class _Cell:
    mix = {"slo": {"ttft_s": 1.0, "tpot_s": 0.1}}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _window(stall_at=None, stall=0.0, refused=0, every=None):
    """Online requests every 0.5 s from t=0..40, each first token 0.2 s
    after due then a token every 0.05 s for 10 tokens; one offline request
    streaming a token every 0.01 s. A stall holds every event after
    ``stall_at`` back by ``stall`` seconds (again each ``every`` seconds)."""
    def shift(t):
        if stall_at is None or t < stall_at:
            return t
        n = 1 + (int((t - stall_at) // every) if every else 0)
        return t + n * stall

    recs = []
    for i in range(80):
        due = 0.5 * i
        times = [shift(due + 0.2 + 0.05 * k) for k in range(10)]
        recs.append(Record("online", due, (1, 2), 10, sent=due, times=times,
                           tokens=[0] * 10, outcome="finished"))
    for i in range(refused):
        recs.append(Record("online", 10.0 + i, (1,), 4, sent=10.0 + i,
                           outcome="refused"))
    off = [shift(0.01 * k) for k in range(4000)]
    recs.append(Record("offline", -5.0, (1,), 4000, sent=-5.0, times=off,
                       tokens=[0] * 4000))
    return RunData(_Cell(), 0, {}, 1.0, 0.0, 40.0, recs, {}, {}, (0, 0, {}))


def test_quiet_window():
    w = _window()
    assert _reader("online_ttft_p90_s")(w) == pytest.approx(0.2)
    assert _reader("online_tpot_p90_s")(w) == pytest.approx(0.05)
    assert _reader("offline_tokens_per_s")(w) == pytest.approx(100.0)
    assert measures.slo_attainment(w) == 1.0


def test_a_stall_moves_tails_and_rate():
    quiet = _window()
    w = _window(stall_at=20.0, stall=3.0)
    assert _reader("online_ttft_p90_s")(w) > 2 * _reader(
        "online_ttft_p90_s")(quiet)
    assert _reader("offline_tokens_per_s")(w) == pytest.approx(
        100.0 * 37 / 40, rel=0.01)
    # a short stall every 0.25 s lands inside most requests' decode
    hiccup = _window(stall_at=0.1, stall=0.15, every=0.25)
    assert _reader("online_tpot_p90_s")(hiccup) > 1.5 * _reader(
        "online_tpot_p90_s")(quiet)


def test_unanswered_and_refused_requests_count():
    w = _window(stall_at=30.0, stall=100.0)
    vals = measures.ttfts(w)
    # requests waiting at the close count at their wait so far
    assert len(vals) == 80
    assert max(vals) == pytest.approx(40.0 - 30.0 + 0.2, abs=0.25)
    r = _window(refused=20)
    v = measures.ttfts(r)
    assert len(v) == 100 and v.count(max(v)) >= 20
    assert _reader("online_ttft_p90_s")(r) == max(v)


def test_tokens_outside_the_window_do_not_count():
    w = _window()
    w.w0, w.w1 = 10.0, 20.0
    assert _reader("offline_tokens_per_s")(w) == pytest.approx(100.0)
    assert len(w.in_window("online")) == 20


def test_the_sample_takes_a_migrated_request_when_the_window_finished_one():
    def rec(n, migrated=False):
        return Record("offline", 0.0, (1, 2), n, times=[1.0 + 0.01 * k
                                                        for k in range(n)],
                      tokens=[0] * n, outcome="finished", migrated=migrated)
    recs = [rec(300)] + [rec(20 + i) for i in range(40)]
    recs[17].migrated = True
    w = RunData(_Cell(), 0, {}, 1.0, 0.0, 40.0, recs, {}, {}, (0, 0, {}))
    for seed in (1, 2, 2 ** 31 + 7):
        picked = check.sample(w, seed)
        assert picked[0] is recs[0] and recs[17] in picked
        assert len(picked) == len({id(r) for r in picked})
        assert sum(len(r.tokens) for r in picked) >= check.SAMPLE_TOKENS
    recs[17].migrated = False
    assert not any(r.migrated for r in check.sample(w, 1))
