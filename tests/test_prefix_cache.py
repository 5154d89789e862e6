"""Cross-request KV reuse (ISSUE 7): refcounted copy-on-write pages, the
radix prefix cache, and the cache-aware roofline.

* ``BlockAllocator`` refcount semantics: double-free / unknown-page /
  reserved-page frees raise ``DoubleFreeError``; shared pages recycle only
  when the LAST owner releases them;
* ``RadixPrefixCache``: block-aligned matching capped below the prompt
  length, deterministic LRU eviction preferring unshared leaves,
  ``touch=False`` planning peeks that do not perturb eviction order;
* engine-level bit parity: a warm prefill that claims cached prefix pages
  produces token streams identical to a cold prefill, request by request;
* cluster: ``select_eviction_victims`` prefers unshared pages and never
  counts shared ones as freed; the cache-aware roofline charges a
  page-table update instead of prefill FLOPs for cached tokens;
* runtime replay: shared-prefix trace with cache on vs off — identical
  ``finished_signature()``, hit counters live in ``summary()``;
* property tests (hypothesis, skip-safe per tests/conftest.py): page-count
  conservation and no-free-while-referenced under arbitrary
  insert/match/evict/abort churn, and cache-on vs cache-off token parity.
"""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import scheduling as sch
from repro.core.hardware import TPU_V5E
from repro.core.perf_model import PerfModel
from repro.core.request import Kind, Phase, Request
from repro.data import traces as tr
from repro.engine.engine import ServingEngine
from repro.engine.kv_cache import (BlockAllocator, DoubleFreeError,
                                   OutOfPagesError, PagedKVCache,
                                   RadixPrefixCache)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2.5-7b").reduced()
    from repro.models.model import build_model
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _ref_generate(model, params, prompt, n_new, margins=None):
    """Greedy whole-model reference. If ``margins`` is a list, the top-2
    logit gap of each generated position is appended to it."""
    import jax.numpy as jnp
    toks = list(prompt)

    def take(logits):
        if margins is not None:
            top2 = jnp.sort(logits[0].astype(jnp.float32))[-2:]
            margins.append(float(top2[1] - top2[0]))
        toks.append(int(jnp.argmax(logits, -1)[0]))

    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)},
        cache_len=len(prompt) + n_new)
    take(logits)
    for _ in range(n_new - 1):
        logits, cache = model.decode_step(
            params, jnp.asarray([toks[-1]], jnp.int32), cache)
        take(logits)
    return toks


# Reference top-2 logit gap below which greedy parity with the whole-model
# reference is not asserted. Both paths round logits to bf16, which tie
# exactly on a few drawn prompts; the engine's chunked paged attention
# rounds differently, so a tie may break the other way. Every flip seen (5
# in 306 generated tokens over 51 drawn workloads) sat at a gap of 0.0;
# 1e-2 is about 2.5 bf16 steps at unit scale.
TIE_GAP = 1e-2


# ---------------------------------------------------------------------------
# BlockAllocator: refcounts + DoubleFreeError (the free() hardening satellite)
# ---------------------------------------------------------------------------
class TestAllocatorRefcounts:
    def test_double_free_raises(self):
        a = BlockAllocator(8, reserved=1)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(DoubleFreeError):
            a.free(pages)

    def test_unknown_and_reserved_pages_raise(self):
        a = BlockAllocator(8, reserved=1)
        with pytest.raises(DoubleFreeError):
            a.free([99])               # out of range
        with pytest.raises(DoubleFreeError):
            a.free([-3])               # out of range, negative
        with pytest.raises(DoubleFreeError):
            a.free([0])                # the reserved trash page
        with pytest.raises(DoubleFreeError):
            a.free([5])                # in range but never allocated

    def test_partial_failure_does_not_corrupt_free_list(self):
        a = BlockAllocator(8, reserved=1)
        pages = a.alloc(2)
        with pytest.raises(DoubleFreeError):
            a.free([pages[0], 99])     # first decrefs, second raises
        assert a.refcount(pages[0]) == 0
        assert a.refcount(pages[1]) == 1
        a.free([pages[1]])
        assert a.free_pages == 7

    def test_shared_page_survives_first_free(self):
        a = BlockAllocator(8, reserved=1)
        [p] = a.alloc(1)
        a.incref([p])
        assert a.refcount(p) == 2
        a.free([p])
        assert a.refcount(p) == 1      # sibling still owns it
        assert p not in a._free
        a.free([p])
        assert a.refcount(p) == 0 and p in a._free

    def test_incref_on_non_live_page_raises(self):
        a = BlockAllocator(8, reserved=1)
        with pytest.raises(DoubleFreeError):
            a.incref([3])              # free page: nothing to share
        [p] = a.alloc(1)
        a.free([p])
        with pytest.raises(DoubleFreeError):
            a.incref([p])              # released page: stale reference

    @given(ops=st.lists(
        st.tuples(st.sampled_from(["alloc", "incref", "free"]),
                  st.integers(0, 5)), max_size=60))
    @settings(max_examples=50, deadline=None)
    def test_conservation_and_no_free_while_referenced(self, ops):
        """Against a pure-python owner model: free_pages + live == capacity
        after every op, refcounts match exactly, and no page sits in the
        free list while an owner still holds it."""
        a = BlockAllocator(32, reserved=1)
        owners: list[list[int]] = []   # one entry per outstanding reference
        for op, n in ops:
            if op == "alloc":
                try:
                    owners.append(a.alloc(n))
                except OutOfPagesError:
                    assert a.free_pages < n
            elif op == "incref" and owners:
                src = owners[n % len(owners)]
                a.incref(src)
                owners.append(list(src))
            elif op == "free" and owners:
                a.free(owners.pop(n % len(owners)))
            refs = {}
            for h in owners:
                for p in h:
                    refs[p] = refs.get(p, 0) + 1
            assert a.free_pages + a.live_pages == 31
            assert a.live_pages == len(refs)
            for p, c in refs.items():
                assert a.refcount(p) == c
                assert p not in a._free   # never freed while referenced


# ---------------------------------------------------------------------------
# RadixPrefixCache unit behaviour
# ---------------------------------------------------------------------------
def _seed_tree(alloc, tree, tokens):
    """Prefill ``tokens`` the way an engine would: alloc pages, insert the
    full ones, release the request's own reference. Returns the table."""
    table = alloc.alloc(-(-len(tokens) // tree.page_size))
    tree.insert(tokens, table)
    alloc.free(table)
    return table


class TestRadixPrefixCache:
    def test_match_is_block_aligned_and_capped(self):
        a = BlockAllocator(16, reserved=1)
        t = RadixPrefixCache(a, page_size=4)
        toks = list(range(12))
        _seed_tree(a, t, toks)
        pages, matched = t.match(toks)             # no cap: all 3 pages
        assert matched == 12 and len(pages) == 3
        pages, matched = t.match(toks, limit=11)   # engine cap: < prompt
        assert matched == 8 and len(pages) == 2    # page-aligned below 11
        pages, matched = t.match(toks[:6] + [99] * 6)
        assert matched == 4                        # diverges in page 2
        assert t.match([7, 7, 7, 7]) == ([], 0)    # cold miss

    def test_existing_nodes_win_on_reinsert(self):
        a = BlockAllocator(16, reserved=1)
        t = RadixPrefixCache(a, page_size=4)
        toks = list(range(8))
        _seed_tree(a, t, toks)
        before = t.resident_pages
        tbl2 = a.alloc(2)
        adopted = t.insert(toks, tbl2)             # duplicate prefill lands
        assert adopted == 0                        # first copy wins
        assert t.resident_pages == before
        a.free(tbl2)                               # private copy released
        assert a.free_pages + a.live_pages == 15

    def test_evict_lru_prefers_unshared(self):
        a = BlockAllocator(32, reserved=1)
        t = RadixPrefixCache(a, page_size=4)
        cold = list(range(100, 104))
        _seed_tree(a, t, cold)                     # oldest, unshared
        hot = list(range(200, 204))
        _seed_tree(a, t, hot)
        hot_pages, _ = t.match(hot)                # refresh + share
        a.incref(hot_pages)                        # a request claims it
        freed = t.evict(1)
        assert freed == 1
        assert t.match(cold, touch=False) == ([], 0)   # LRU unshared gone
        assert t.match(hot, touch=False)[1] == 4       # shared one kept
        # evicting past the unshared supply drops shared leaves (decref
        # only) without counting them as freed
        assert t.evict(1) == 0
        assert a.refcount(hot_pages[0]) == 1       # request still owns it

    def test_planning_peek_does_not_perturb_lru(self):
        a = BlockAllocator(32, reserved=1)
        t = RadixPrefixCache(a, page_size=4)
        first = list(range(4))
        second = list(range(10, 14))
        _seed_tree(a, t, first)
        _seed_tree(a, t, second)
        t.match(first, touch=False)                # gating peek: no refresh
        t.evict(1)
        assert t.match(first, touch=False) == ([], 0)  # still the LRU victim
        assert t.match(second, touch=False)[1] == 4

    def test_clear_drops_tree_without_touching_allocator(self):
        a = BlockAllocator(16, reserved=1)
        t = RadixPrefixCache(a, page_size=4)
        _seed_tree(a, t, list(range(8)))
        free_before = a.free_pages
        t.clear()                                  # crash path
        assert t.resident_pages == 0
        assert a.free_pages == free_before         # allocator untouched

    @given(seq=st.lists(
        st.tuples(st.sampled_from(["prefill", "claim", "release", "evict"]),
                  st.integers(0, 7)), max_size=40))
    @settings(max_examples=40, deadline=None)
    def test_conservation_under_churn(self, seq):
        """Arbitrary insert/match/evict/abort sequences: page counts are
        conserved, every tree page stays live, and no page held by a
        request's table is ever recycled out from under it."""
        a = BlockAllocator(24, reserved=1)
        t = RadixPrefixCache(a, page_size=4)
        prompts = [[b, b + 1, b + 2, b + 3, b + 4]
                   for b in range(0, 80, 10)]      # 1 full + 1 partial page
        claims: list[list[int]] = []
        for op, n in seq:
            if op == "prefill":
                toks = prompts[n % len(prompts)]
                try:
                    table = a.alloc(2)
                except OutOfPagesError:
                    continue
                t.insert(toks, table)
                a.free(table)                      # request aborts/finishes
            elif op == "claim":
                pages, m = t.match(prompts[n % len(prompts)])
                if m:
                    a.incref(pages)
                    claims.append(pages)
            elif op == "release" and claims:
                a.free(claims.pop(n % len(claims)))
            elif op == "evict":
                t.evict(n)
            assert a.free_pages + a.live_pages == 23
            held = {p for c in claims for p in c}
            for p in held:
                assert a.refcount(p) >= 1
                assert p not in a._free
            # every resident tree page is live
            stack = list(t.root.children.values())
            while stack:
                node = stack.pop()
                assert a.refcount(node.page) >= 1
                stack.extend(node.children.values())
        for c in claims:
            a.free(c)
        t.evict(a.num_pages)
        assert a.free_pages == 23                  # everything drains


# ---------------------------------------------------------------------------
# PagedKVCache: adopt / available_pages / pressure eviction / shared_tokens
# ---------------------------------------------------------------------------
class TestPagedKVCacheSharing:
    def _cache(self, cfg, pages=16):
        return PagedKVCache(cfg, pages, page_size=4, enable_prefix_cache=True)

    def test_adopt_increfs_and_seeds_table(self, setup):
        cfg, _, _ = setup
        c = self._cache(cfg)
        toks = list(range(9))
        c.ensure(1, 9)
        c.prefix.insert(toks, c.tables[1])
        pages, matched = c.prefix.match(toks, limit=8)
        c.adopt(2, pages, matched)
        assert c.tables[2] == c.tables[1][:2] and c.lengths[2] == 8
        assert all(c.allocator.refcount(p) == 3 for p in pages)
        assert c.shared_tokens(1) == 8 and c.shared_tokens(2) == 8
        with pytest.raises(AssertionError):
            c.adopt(2, pages, matched)             # already holds pages

    def test_available_pages_counts_reclaimable_and_ensure_evicts(self, setup):
        cfg, _, _ = setup
        c = self._cache(cfg, pages=9)              # 8 usable
        c.ensure(1, 32)                            # all 8 pages
        c.prefix.insert(list(range(32)), c.tables[1])
        c.free(1)                                  # tree holds all 8 now
        assert c.allocator.free_pages == 0
        assert c.available_pages == 8              # all reclaimable
        assert c.can_fit(12)
        c.ensure(2, 12)                            # forces tree eviction
        assert len(c.tables[2]) == 3
        assert c.prefix.evictions >= 3

    def test_free_is_a_decref_not_a_release(self, setup):
        cfg, _, _ = setup
        c = self._cache(cfg)
        toks = list(range(8))
        c.ensure(1, 8)
        c.prefix.insert(toks, c.tables[1])
        free_before = c.allocator.free_pages
        c.free(1)                                  # request done
        assert c.allocator.free_pages == free_before   # tree keeps both
        assert c.prefix.match(toks, touch=False)[1] == 8


# ---------------------------------------------------------------------------
# Engine: warm prefill bit-parity with cold prefill (the correctness bar)
# ---------------------------------------------------------------------------
class TestEngineWarmColdParity:
    def test_claimed_prefix_tokens_bit_identical(self, setup):
        """The tentpole invariant: greedy token streams with the cache on
        are bit-identical to a cold prefill, request by request."""
        cfg, model, params = setup
        rng = np.random.RandomState(11)
        shared = list(rng.randint(0, cfg.vocab_size, 16))
        pa = shared + list(rng.randint(0, cfg.vocab_size, 8))
        pb = shared + list(rng.randint(0, cfg.vocab_size, 8))
        ref_a = _ref_generate(model, params, pa, 6)
        ref_b = _ref_generate(model, params, pb, 6)
        eng = ServingEngine(model, params, num_pages=64, page_size=8,
                            prefix_cache=True)
        ra = Request(Kind.OFFLINE, 0.0, len(pa), 6)
        eng.add_request(ra, pa)
        while ra.generated == 0:
            eng.mixed_step([], ra.rid, 8)
        while not ra.done:
            eng.decode_step([ra.rid])
        assert eng.token_buf[ra.rid] == ref_a      # cold path unchanged
        assert eng.cache.prefix.resident_pages == 3   # 24-token prompt
        rb = Request(Kind.OFFLINE, 0.0, len(pb), 6)
        eng.add_request(rb, pb)
        assert eng.claim_prefix(rb.rid) == 16      # 2 shared pages
        assert rb.cached_tokens == 16
        assert rb.prefill_tokens_done == 16        # resumes at the boundary
        assert eng.claim_prefix(rb.rid) == 0       # idempotent: in flight
        while rb.generated == 0:
            eng.mixed_step([], rb.rid, 8)          # only the 8-token suffix
        while not rb.done:
            eng.decode_step([rb.rid])
        assert eng.token_buf[rb.rid] == ref_b      # bit-identical warm path
        assert eng.stats.prefix_hits == 1
        assert eng.stats.cached_tokens == 16
        assert eng.stats.shared_pages == 2

    def test_legacy_prefill_refuses_warm_started_request(self, setup):
        """The whole-table prefill path would rewrite shared pages; it must
        refuse a request that already claimed cached pages."""
        cfg, model, params = setup
        rng = np.random.RandomState(12)
        prompt = list(rng.randint(0, cfg.vocab_size, 17))
        eng = ServingEngine(model, params, num_pages=64, page_size=8,
                            prefix_cache=True)
        ra = Request(Kind.OFFLINE, 0.0, len(prompt), 2)
        eng.add_request(ra, prompt)
        while ra.generated == 0:
            eng.mixed_step([], ra.rid, 8)
        rb = Request(Kind.OFFLINE, 0.0, len(prompt), 2)
        eng.add_request(rb, prompt)
        assert eng.claim_prefix(rb.rid) == 16
        with pytest.raises(AssertionError):
            eng.prefill(rb.rid)

    def test_abort_after_claim_charges_only_computed_tokens(self, setup):
        """Recompute accounting: cached tokens were never computed here, so
        aborting a warm prefill wastes only what it actually ran."""
        cfg, model, params = setup
        rng = np.random.RandomState(13)
        prompt = list(rng.randint(0, cfg.vocab_size, 28))
        eng = ServingEngine(model, params, num_pages=64, page_size=8,
                            prefix_cache=True)
        ra = Request(Kind.OFFLINE, 0.0, len(prompt), 2)
        eng.add_request(ra, prompt)
        while ra.generated == 0:
            eng.mixed_step([], ra.rid, 8)
        rb = Request(Kind.OFFLINE, 0.0, len(prompt), 2)
        eng.add_request(rb, prompt)
        assert eng.claim_prefix(rb.rid) == 24      # capped below prompt_len
        eng.mixed_step([], rb.rid, 2)              # 2 of the 4-token suffix
        eng.abort_prefill(rb.rid)
        assert rb.recompute_tokens == 2            # not 26
        assert rb.cached_tokens == 0 and rb.prefill_tokens_done == 0
        ref = _ref_generate(model, params, prompt, 2)
        while rb.generated == 0:                   # re-claims and resumes
            eng.mixed_step([], rb.rid, 8)
        while not rb.done:
            eng.decode_step([rb.rid])
        assert eng.token_buf[rb.rid] == ref

    def test_crash_drops_tree(self, setup):
        cfg, model, params = setup
        rng = np.random.RandomState(14)
        prompt = list(rng.randint(0, cfg.vocab_size, 16))
        eng = ServingEngine(model, params, num_pages=64, page_size=8,
                            prefix_cache=True)
        r = Request(Kind.OFFLINE, 0.0, len(prompt), 2)
        eng.add_request(r, prompt)
        while r.generated == 0:
            eng.mixed_step([], r.rid, 8)
        assert eng.cache.prefix.resident_pages > 0
        eng.crash()
        assert eng.cache.prefix.resident_pages == 0

    @given(data=st.data())
    @settings(max_examples=5, deadline=None)
    def test_property_cache_on_matches_cold_reference(self, setup, data):
        """Random shared-prefix workloads through one warm engine: every
        greedy stream equals its cold whole-prompt reference up to the
        first near-tied reference token (``TIE_GAP``), and equals the same
        engine run cold (cache off) bit for bit."""
        cfg, model, params = setup
        vocab = cfg.vocab_size
        rng = np.random.RandomState(
            data.draw(st.integers(0, 2 ** 16), label="seed"))
        shared = list(rng.randint(0, vocab, 8 * data.draw(
            st.integers(1, 3), label="prefix_pages")))
        n_reqs = data.draw(st.integers(2, 4), label="n_reqs")
        warm = ServingEngine(model, params, num_pages=96, page_size=8,
                             prefix_cache=True)
        cold = ServingEngine(model, params, num_pages=96, page_size=8,
                             kernels_from=warm)

        def generate(eng, prompt):
            r = Request(Kind.OFFLINE, 0.0, len(prompt), 3)
            eng.add_request(r, prompt)
            while r.generated == 0:
                eng.mixed_step([], r.rid, 8)
            while not r.done:
                eng.decode_step([r.rid])
            return eng.token_buf[r.rid].tolist()

        for _ in range(n_reqs):
            suffix = list(rng.randint(0, vocab,
                                      int(rng.randint(1, 10))))
            prompt = shared + suffix
            margins: list[float] = []
            ref = _ref_generate(model, params, prompt, 3, margins)
            got = generate(warm, prompt)
            assert got == generate(cold, prompt)
            n = len(prompt) + next(
                (i for i, m in enumerate(margins) if m < TIE_GAP), 3)
            assert got[:n] == ref[:n]
        assert warm.stats.prefix_hits >= n_reqs - 1
        assert cold.stats.prefix_hits == 0


# ---------------------------------------------------------------------------
# Scheduling: eviction prefers unshared pages; roofline knows about hits
# ---------------------------------------------------------------------------
def _req(prompt, generated=0):
    r = Request(Kind.OFFLINE, 0.0, prompt, 64)
    r.prefill_tokens_done = prompt
    r.generated = generated
    return r


class TestEvictionPrefersUnshared:
    def test_shared_requests_evicted_last(self):
        shared_r, private_r = _req(256), _req(256)
        shared = {shared_r.rid: 256, private_r.rid: 0}
        for bn in ("memory", "compute"):
            victims = sch.select_eviction_victims(
                [shared_r, private_r], 128, bn, shared_tokens=shared)
            assert victims == [private_r], bn

    def test_fully_shared_frees_nothing(self):
        """A request whose pages are all shared releases zero tokens —
        victim selection must keep evicting until real space is freed."""
        a, b, c = _req(128), _req(128), _req(128)
        shared = {a.rid: 128, b.rid: 0, c.rid: 0}
        victims = sch.select_eviction_victims(
            [a, b, c], 200, "memory", shared_tokens=shared)
        assert a not in victims
        assert sorted(r.rid for r in victims) == sorted([b.rid, c.rid])

    def test_partial_sharing_counts_only_releasable(self):
        a, b = _req(256), _req(160)
        shared = {a.rid: 192, b.rid: 0}            # a releases only 64
        victims = sch.select_eviction_victims(
            [a, b], 150, "compute", shared_tokens=shared)
        assert victims[0] is b                     # 160 releasable > 64

    def test_without_shared_map_behaviour_is_legacy(self):
        reqs = [_req(64), _req(256), _req(128)]
        for bn in ("memory", "compute"):
            legacy = sch.select_eviction_victims(list(reqs), 100, bn)
            with_none = sch.select_eviction_victims(
                list(reqs), 100, bn, shared_tokens=None)
            empty = sch.select_eviction_victims(
                list(reqs), 100, bn, shared_tokens={})
            assert legacy == with_none == empty


class TestCacheAwareRoofline:
    @pytest.fixture(scope="class")
    def pm(self, setup):
        return PerfModel(setup[0], TPU_V5E)

    def test_cached_tokens_cut_prefill_flops(self, pm):
        cold = pm.prefill_estimate([512])
        warm = pm.prefill_estimate([512], [384])
        assert warm.flops < cold.flops * 0.5
        assert warm.latency < cold.latency
        page_ops = [o for o in warm.ops if o.name == "page_table"]
        assert len(page_ops) == 1 and page_ops[0].flops == 0.0

    def test_hit_never_covers_whole_prompt(self, pm):
        clamped = pm.prefill_estimate([64], [64])
        assert clamped.flops == pm.prefill_estimate([64], [63]).flops
        assert clamped.flops > 0                   # >= 1 token computed

    def test_defaults_are_legacy_identical(self, pm):
        assert pm.prefill_estimate([128]).latency == \
            pm.prefill_estimate([128], [0]).latency
        assert pm.mixed_estimate(32, 96, (64, 80)).latency == \
            pm.mixed_estimate(32, 96, (64, 80), cached_tokens=0).latency

    def test_mixed_estimate_cached_context(self, pm):
        cold = pm.mixed_estimate(32, 512, (64,))
        warm = pm.mixed_estimate(32, 512, (64,), cached_tokens=448)
        assert warm.kv_bytes < cold.kv_bytes       # only the suffix is new
        assert warm.flops == cold.flops            # attention span unchanged
        # page-table bookkeeping is noise next to the dispatch overhead
        assert abs(warm.latency - cold.latency) < 1e-3 * cold.latency

    def test_gating_admits_warm_candidate_under_memory_pressure(self, pm):
        """Shared pages are already resident: only the uncached suffix
        counts against the admission memory budget."""
        cand = Request(Kind.OFFLINE, 0.0, 512, 32)
        budget = pm.kv_bytes([256])                # < full prompt, > suffix
        cold = sch.gating_decision(cand, [], pm, evict_probability=0.5,
                                   horizon_seconds=1.0,
                                   mem_budget_bytes=budget)
        warm = sch.gating_decision(cand, [], pm, evict_probability=0.5,
                                   horizon_seconds=1.0,
                                   mem_budget_bytes=budget,
                                   cached_tokens=448)
        assert not cold and warm


# ---------------------------------------------------------------------------
# Runtime: shared-prefix replay parity + counters in summary()
# ---------------------------------------------------------------------------
class TestRuntimeSharedPrefixReplay:
    @pytest.fixture(scope="class")
    def runs(self, setup):
        cfg, model, params = setup
        reqs = tr.shared_prefix_requests(
            num_prefixes=2, variants=2, queries=3, prefix_tokens=24,
            variant_tokens=8, query_tokens=8, output_len=3,
            vocab=cfg.vocab_size, seed=5)
        offline = tr.with_uniform_qps(reqs, 6.0)
        out, donor = {}, None
        for name, on in (("on", True), ("off", False)):
            from repro.cluster.runtime import (PoolRuntime, VirtualClock,
                                               replay_hw)
            rt = PoolRuntime(cfg, policy="ooco", n_strict=1, n_relaxed=1,
                             clock=VirtualClock(), backend="ref",
                             num_pages=128, page_size=8, hw=replay_hw(),
                             model=model, params=params,
                             chunk_tokens="auto", prefix_cache=on,
                             kernels_from=donor)
            donor = donor or rt.kernel_donor
            summary = rt.run([], offline, duration=12.0, max_prompt=48,
                             max_output=4, drain=True)
            out[name] = (summary, rt.finished_signature())
        return out

    def test_token_streams_bit_identical(self, runs):
        s_on, sig_on = runs["on"]
        s_off, sig_off = runs["off"]
        assert sig_on and sig_on == sig_off        # request-by-request
        assert s_on["offline_finished"] == s_off["offline_finished"] > 0

    def test_hit_counters_surface_in_summary(self, runs):
        s_on, _ = runs["on"]
        s_off, _ = runs["off"]
        assert s_on["prefix_cache"] and not s_off["prefix_cache"]
        assert s_on["prefix_hits"] > 0
        assert s_on["cached_tokens"] > 0
        assert s_on["shared_pages"] > 0
        assert s_on["prefix_evictions"] >= 0
        assert s_off["prefix_hits"] == s_off["cached_tokens"] == 0
        # same prompt tokens served, strictly less modeled prefill compute:
        # the effective-throughput ratio the prefix_reuse bench gates on
        assert s_on["prefill_tokens"] == s_off["prefill_tokens"] > 0
        assert s_on["prefill_modeled_seconds"] < \
            s_off["prefill_modeled_seconds"]
