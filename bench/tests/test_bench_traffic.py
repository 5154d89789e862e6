"""The traffic generator: one seed, one schedule; every seed the same work."""

# benchtest first: it puts bench/ and src/ on the import path
import benchtest  # noqa: F401
import numpy as np

from harness import traffic

MIX = {"ramp_s": 20,
       "online": {"rate_per_s": 1.6, "prompt_mean": 1512.3,
                  "output_mean": 98.75, "cov": 1.0, "prompt_cap": 8192,
                  "output_cap": 4096,
                  "bursts": [{"at_frac": 0.4, "seconds": 5, "mult": 3.0}]},
       "offline": {"count": 400, "prompt_mean": 1200.52,
                   "output_mean": 671.51, "cov": 1.0, "prompt_cap": 8192,
                   "output_cap": 4096}}
BIG = 2 ** 31 + 12345


def _key(reqs):
    return [(r.kind, r.due, r.prompt, r.output_len) for r in reqs]


def test_same_seed_same_schedule():
    assert _key(traffic.generate(MIX, BIG, 45, 1000)) == _key(
        traffic.generate(MIX, BIG, 45, 1000))
    assert _key(traffic.generate(MIX, BIG, 45, 1000)) != _key(
        traffic.generate(MIX, BIG + 1, 45, 1000))


def test_every_seed_gets_the_same_sizes_and_gaps():
    a = traffic.generate(MIX, 3, 45, 1000)
    b = traffic.generate(MIX, BIG, 45, 1000)
    for kind in ("online", "offline"):
        ra = [r for r in a if r.kind == kind]
        rb = [r for r in b if r.kind == kind]
        assert sorted(len(r.prompt) for r in ra) == sorted(
            len(r.prompt) for r in rb)
        assert sorted(r.output_len for r in ra) == sorted(
            r.output_len for r in rb)
    da = np.diff([r.due for r in a if r.kind == "online"])
    db = np.diff([r.due for r in b if r.kind == "online"])
    assert len(da) == len(db)
    assert max(r.due for r in a) < MIX["ramp_s"] + 45


def test_lengths_follow_the_stated_means_and_caps():
    reqs = traffic.generate(MIX, 11, 45, 1000)
    off = [r for r in reqs if r.kind == "offline"]
    p = np.array([len(r.prompt) for r in off])
    o = np.array([r.output_len for r in off])
    assert p.max() <= 8192 and o.max() <= 4096
    assert p.min() >= traffic.MIN_LEN and o.min() >= traffic.MIN_LEN
    # the caps clip the far tail only: the means stay within 3%
    assert abs(p.mean() / 1200.52 - 1) < 0.03
    assert abs(o.mean() / 671.51 - 1) < 0.03
    assert all(0 <= t < 1000 for r in off for t in r.prompt)


def test_online_rate_and_burst():
    reqs = traffic.generate(MIX, 5, 45, 1000)
    due = np.array([r.due for r in reqs if r.kind == "online"])
    base = round(1.6 * 65)
    extra = round(2.0 * 1.6 * 5)
    assert len(due) == base + extra
    b0 = 20 + 0.4 * 45
    in_burst = ((due >= b0) & (due < b0 + 5)).sum()
    assert in_burst >= extra + 0.5 * 1.6 * 5
    assert all(r.due == 0.0 for r in reqs if r.kind == "offline")


def test_quantiles_match_the_lognormal():
    q = traffic.lognormal_quantiles(1000.0, 1.0, 20000, 1, 10 ** 9)
    assert abs(q.mean() / 1000.0 - 1) < 0.01
    assert abs(q.std() / q.mean() - 1) < 0.05


def test_every_head_of_the_batch_holds_the_same_mix():
    """The runtime takes offline work first in, first out, so a window sees
    the head of the batch: every seed gets the batch's lengths in one
    order, whose every head takes its share of each tenth of them."""
    n = 600
    for step in traffic.STEPS:
        order = traffic.spread(n, step)
        assert sorted(order) == list(range(n))
        for k in (50, 100, 300):
            counts, _ = np.histogram(order[:k], bins=10, range=(0, n))
            assert counts.min() >= k / 10 - 2, (k, counts)
            assert counts.max() <= k / 10 + 2, (k, counts)
    a, b = (traffic.generate(MIX, seed, 45, 1000) for seed in (3, BIG))
    off_a = [r for r in a if r.kind == "offline"]
    off_b = [r for r in b if r.kind == "offline"]
    assert [(len(r.prompt), r.output_len) for r in off_a] == [
        (len(r.prompt), r.output_len) for r in off_b]
    assert [r.prompt for r in off_a] != [r.prompt for r in off_b]
    full = np.median([r.output_len for r in off_a])
    head = np.median([r.output_len for r in off_a[:100]])
    assert abs(head - full) < 0.1 * full
