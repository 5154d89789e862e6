"""Pool-runtime co-location suite (ISSUE 3).

* virtual-clock trace replay is bit-deterministic: same seed → identical
  finished-request set, token streams, and metric values across runs;
* policy SLO discrimination on a bursty synthetic trace: ``ooco`` meets the
  TPOT SLO while ``base_pd`` does not, and ``ooco`` beats
  ``online_priority`` on offline tokens/s at equal-or-better attainment;
* arbitrary N-strict + M-relaxed topologies drain their traces;
* property tests (hypothesis, skip-safe per tests/conftest.py) for the
  scheduling points the runtime routes through: eviction victims always
  free enough and never include online work, mix-decoding batches never
  exceed the SLO bound under the perf model.
"""
import json
import os
import subprocess
import sys

import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro.cluster.runtime import PoolRuntime, VirtualClock, replay_hw
from repro.configs import get_config
from repro.core import scheduling as sch
from repro.core.perf_model import PerfModel
from repro.core.request import Kind, Request
from repro.data import traces as tr
from repro.models.model import build_model

SLO_TTFT = 1.0
SLO_TPOT = 0.030


@pytest.fixture(scope="module")
def built():
    cfg = get_config("qwen2.5-7b").reduced()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, [None]   # last slot: shared kernel donor


def _replay(built, policy, *, seed=0, n_strict=1, n_relaxed=2,
            n_offline=100, offline_qps=20.0, online_qps=1.2, duration=10.0,
            max_output=12, drain=False, chunk_tokens="auto"):
    """Deterministic virtual-clock replay of a bursty synthetic trace.

    Defaults use a fixed evaluation window under a saturating offline
    backlog (the §5.2 protocol): every policy gets the same window, so
    offline tokens/s measures what the policy extracted at its SLO
    attainment. Chunked prefill is on by default (the production path);
    ``chunk_tokens=0`` replays through the legacy whole-prompt prefill."""
    cfg, model, params, donor = built
    rt = PoolRuntime(cfg, policy=policy, n_strict=n_strict,
                     n_relaxed=n_relaxed, clock=VirtualClock(), backend="ref",
                     num_pages=256, page_size=8, slo_ttft=SLO_TTFT,
                     slo_tpot=SLO_TPOT, hw=replay_hw(), seed=seed,
                     model=model, params=params, chunk_tokens=chunk_tokens,
                     kernels_from=donor[0])
    donor[0] = donor[0] or rt.kernel_donor
    online = tr.online_trace("ooc", duration=duration, mean_qps=online_qps,
                             seed=seed)
    offline = tr.with_uniform_qps(
        tr.offline_requests(n_offline, seed=seed + 1), offline_qps)
    summary = rt.run(online, offline, duration=duration, max_prompt=48,
                     max_output=max_output, drain=drain)
    return summary, rt


@pytest.fixture(scope="module")
def policy_runs(built):
    return {p: _replay(built, p)
            for p in ("ooco", "base_pd", "online_priority")}


class TestVirtualClockDeterminism:
    def test_replay_is_bit_deterministic(self, built, policy_runs):
        m1, rt1 = policy_runs["ooco"]
        m2, rt2 = _replay(built, "ooco")   # fresh runtime, fresh engines
        assert m1 == m2                    # every metric value identical
        assert rt1.finished_signature() == rt2.finished_signature()
        # the signature covers the finished set AND full token streams
        assert len(rt1.finished_signature()) == len(rt1.finished)
        assert rt1.finished

    def test_replay_work_actually_happened(self, policy_runs):
        m, rt = policy_runs["ooco"]
        assert m["online_finished"] == m["online_requests"] > 0
        assert m["offline_finished"] > 0
        assert m["offline_tokens"] > 0
        assert all(len(toks) > 0 for toks in rt.tokens.values())


class TestPolicyDiscrimination:
    def test_ooco_meets_tpot_slo_base_pd_does_not(self, policy_runs):
        ooco, _ = policy_runs["ooco"]
        base, _ = policy_runs["base_pd"]
        assert ooco["online_tpot_p99"] <= SLO_TPOT * (1 + 1e-9)
        assert base["online_tpot_p99"] > SLO_TPOT
        assert ooco["online_slo_attainment"] > base["online_slo_attainment"]

    def test_ooco_beats_online_priority_offline_throughput(self, policy_runs):
        ooco, _ = policy_runs["ooco"]
        op, _ = policy_runs["online_priority"]
        assert ooco["online_slo_attainment"] >= op["online_slo_attainment"]
        assert ooco["offline_tokens_per_s"] > op["offline_tokens_per_s"]

    def test_ooco_exercises_cluster_mechanisms(self, policy_runs):
        """The §3.4 machinery must actually fire on the real path."""
        m, _ = policy_runs["ooco"]
        assert m["migrations"] > 0          # real relaxed→strict KV movement
        assert m["pulls"] > 0               # §3.4.3 pull-model migration

    def test_baselines_do_not_pull_or_preempt(self, policy_runs):
        for p in ("base_pd", "online_priority"):
            m, _ = policy_runs[p]
            assert m["pulls"] == 0
            assert m["preemptions"] == 0

    def test_virtual_clock_layer_preemption_fires(self, built):
        """§3.4.1 under the virtual clock: an online arrival landing inside
        an offline prefill window interrupts it at a layer boundary —
        deterministically, with no wall-clock involvement."""
        cfg, model, params, donor = built
        rt = PoolRuntime(cfg, policy="ooco", n_strict=1, n_relaxed=1,
                         clock=VirtualClock(), backend="ref", num_pages=128,
                         page_size=8, slo_ttft=SLO_TTFT, slo_tpot=SLO_TPOT,
                         hw=replay_hw(), seed=0, model=model, params=params,
                         kernels_from=donor[0])
        offline = [tr.TraceRequest(0.0, 48, 4)]
        online = [tr.TraceRequest(0.005, 16, 4)]   # mid-prefill arrival
        m = rt.run(online, offline, duration=2.0, max_prompt=48, max_output=4)
        assert m["preemptions"] >= 1
        assert m["online_finished"] == 1 and m["offline_finished"] == 1


class TestChunkedPrefill:
    """Chunked prefill + fused mixed steps through the pool runtime:
    §3.4.1 preemption at deterministic chunk boundaries under the virtual
    clock, bit-identical replay with chunking on, and the TTFT payoff vs
    whole-prompt prefill on a bursty trace."""

    def test_chunked_replay_bit_deterministic(self, built):
        m1, rt1 = _replay(built, "ooco", chunk_tokens=8)
        m2, rt2 = _replay(built, "ooco", chunk_tokens=8)
        assert m1 == m2
        assert rt1.finished_signature() == rt2.finished_signature()
        assert m1["chunks"] > 0                 # the fused path actually ran

    def test_chunk_boundary_preemption_fires(self, built):
        """An online arrival landing inside a long offline prefill pauses it
        at the next chunk boundary — deterministically, with the offline
        request keeping its landed prefix (no layer re-execution: zero
        recompute tokens)."""
        cfg, model, params, donor = built
        rt = PoolRuntime(cfg, policy="ooco", n_strict=1, n_relaxed=1,
                         clock=VirtualClock(), backend="ref", num_pages=128,
                         page_size=8, slo_ttft=SLO_TTFT, slo_tpot=SLO_TPOT,
                         hw=replay_hw(), seed=0, model=model, params=params,
                         chunk_tokens=8, kernels_from=donor[0])
        offline = [tr.TraceRequest(0.0, 48, 4)]
        online = [tr.TraceRequest(0.005, 16, 4)]   # mid-prefill arrival
        m = rt.run(online, offline, duration=2.0, max_prompt=48, max_output=4)
        assert m["chunk_preemptions"] >= 1
        assert m["preemptions"] >= 1               # unified §3.4.1 counter
        assert m["online_finished"] == 1 and m["offline_finished"] == 1
        assert m["recompute_tokens"] == 0          # paused, never re-run

    def test_chunked_ttft_beats_whole_prompt_prefill(self, built):
        """On the bursty co-location trace, chunk-boundary preemption must
        tighten online TTFT vs the legacy whole-prompt path at no offline
        throughput cost (the ISSUE's headline tradeoff)."""
        chunked, _ = _replay(built, "ooco", chunk_tokens="auto")
        legacy, _ = _replay(built, "ooco", chunk_tokens=0)
        assert chunked["online_ttft_p99"] < legacy["online_ttft_p99"]
        assert chunked["online_ttft_p50"] < legacy["online_ttft_p50"]
        assert (chunked["offline_tokens_per_s"]
                >= legacy["offline_tokens_per_s"] * (1 - 1e-9))
        assert chunked["online_slo_attainment"] >= legacy["online_slo_attainment"]

    def test_fixed_budget_cli_value_drains(self, built):
        """A fixed --chunk-tokens N budget (not auto) still drains a mixed
        trace with every request finished."""
        m, rt = _replay(built, "ooco", chunk_tokens=16, n_offline=16,
                        offline_qps=50.0, duration=6.0, drain=True)
        assert m["offline_finished"] == m["offline_requests"]
        assert m["online_finished"] == m["online_requests"]
        assert m["chunks"] > 0


class TestTopology:
    def test_multi_strict_multi_relaxed_drains(self, built):
        # an offline burst at t=0 plus steady online traffic spreads work
        # over every engine of a 2-strict + 2-relaxed topology
        m, rt = _replay(built, "ooco", n_strict=2, n_relaxed=2,
                        n_offline=16, offline_qps=50.0, online_qps=2.0,
                        duration=6.0, max_output=8)
        assert m["online_finished"] == m["online_requests"] > 0
        assert m["offline_finished"] == m["offline_requests"]
        assert m["migrations"] > 0
        assert all(s.engine.stats.decode_steps > 0 for s in rt.strict_pool)
        assert all(s.engine.stats.prefill_tokens > 0 for s in rt.relaxed_pool)

    def test_engines_spread_over_devices_keep_token_parity(self):
        """With several devices, the runtime puts one engine on each (the
        weights replicated, the KV pools committed there) and migrates KV
        across devices; token streams equal the all-on-one-device replay.
        Runs on four forced host devices in a CPU-only subprocess."""
        code = """
import json
import jax
from repro.cluster.runtime import PoolRuntime, VirtualClock, replay_hw
from repro.configs import get_config
from repro.data import traces as tr
from repro.models.model import build_model

cfg = get_config("qwen2.5-7b").reduced()
model = build_model(cfg, remat=False)
online = tr.online_trace("ooc", duration=4.0, mean_qps=2.0, seed=0)
offline = tr.with_uniform_qps(tr.offline_requests(8, seed=1), 4.0)

def replay(params):
    rt = PoolRuntime(cfg, n_strict=1, n_relaxed=3, clock=VirtualClock(),
                     backend="ref", num_pages=128, page_size=8,
                     slo_ttft=1.0, slo_tpot=0.03, hw=replay_hw(),
                     model=model if params is not None else None,
                     params=params)
    m = rt.run(online, offline, duration=4.0, max_prompt=32, max_output=6)
    devs = [s.engine.cache.k_pool.devices().pop().id
            for s in rt.strict_pool + rt.relaxed_pool]
    return devs, rt.finished_signature(), m

one = replay(model.init(jax.random.PRNGKey(0)))
four = replay(None)
print(json.dumps({"one_devs": one[0], "four_devs": four[0],
                  "same": one[1] == four[1], "finished": len(four[1]),
                  "migrations": four[2]["migrations"]}))
"""
        env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
               "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=600, env=env)
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["one_devs"] == [0, 0, 0, 0]
        assert res["four_devs"] == [0, 1, 2, 3]
        assert res["finished"] > 0 and res["migrations"] > 0
        assert res["same"]


# ---------------------------------------------------------------------------
# property tests for the scheduling points the runtime routes through
# ---------------------------------------------------------------------------

_PM = PerfModel(get_config("qwen2.5-7b").reduced(), replay_hw())


def _reqs(kind, lens):
    return [Request(kind, 0.0, int(max(l, 1)), 8) for l in lens]


class TestSchedulingProperties:
    @given(off=st.lists(st.integers(1, 4096), min_size=0, max_size=24),
           on=st.lists(st.integers(1, 4096), min_size=0, max_size=8),
           need=st.integers(1, 60000),
           bn=st.sampled_from(["compute", "memory", "balanced"]))
    @settings(max_examples=60, deadline=None)
    def test_eviction_frees_enough_and_never_online(self, off, on, need, bn):
        """Victims free >= the requested tokens (or are ALL offline work),
        and never include an online request even on a mixed resident list."""
        mixed = _reqs(Kind.OFFLINE, off) + _reqs(Kind.ONLINE, on)
        victims = sch.select_eviction_victims(mixed, need, bn)
        assert all(v.kind is Kind.OFFLINE for v in victims)
        freed = sum(v.context_len for v in victims)
        n_offline = sum(1 for r in mixed if r.kind is Kind.OFFLINE)
        assert freed >= need or len(victims) == n_offline
        ids = [v.rid for v in victims]
        assert len(set(ids)) == len(ids)

    @given(on=st.lists(st.integers(1, 2048), min_size=0, max_size=6),
           off=st.lists(st.integers(1, 2048), min_size=0, max_size=24),
           seed=st.integers(0, 7))
    @settings(max_examples=60, deadline=None)
    def test_mix_decoding_respects_slo_bound(self, on, off, seed):
        """All online requests always ride; any admitted offline keeps the
        perf-model-predicted step latency within the TPOT SLO."""
        import random
        online = _reqs(Kind.ONLINE, on)
        offline = _reqs(Kind.OFFLINE, off)
        batch = sch.mix_decoding_selection(online, offline, SLO_TPOT, _PM,
                                           rng=random.Random(seed))
        assert batch[: len(online)] == online
        ids = [r.rid for r in batch]
        assert len(set(ids)) == len(ids)
        if len(batch) > len(online):
            lat = _PM.decode_estimate([r.context_len for r in batch]).latency
            assert lat <= SLO_TPOT * (1 + 1e-9)
