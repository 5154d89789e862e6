"""Serving engine: continuous batching + paged KV + layer-interruptible prefill.

One ``ServingEngine`` is an xllm-instance analogue (DESIGN §3): it holds the
model weights once and can run Prefill and/or Decode iterations. The paper's
two mechanisms are implemented for real, not simulated:

* **Chunked prefill + fused mixed steps** (§3.4.1 boundary granularity):
  ``mixed_step(decode_rids, prefill_rid, chunk_tokens)`` advances a prompt by
  a token-budgeted chunk INSIDE the same jitted dispatch that decodes the
  resident batch. The chunk's K/V scatters into the donated paged pools
  first, then the (length-bucketed) query block attends over the request's
  gathered pages — everything already landed plus itself — with causal
  ``q_offset``/per-row ``kv_lens`` masking, so one trace serves every
  (chunk length, context) bucket. Between chunks the only state is the
  count of landed tokens (``ChunkedPrefill``): pausing costs nothing and a
  resume re-runs no layer. Decode-side attention keeps the backend paged
  kernel dispatch; the chunk side uses the XLA flash path on every backend
  (the Pallas prefill kernel's offsets are compile-time — see ROADMAP).
* **Layer-level interruption** (§3.4.1, legacy path): whole-prompt
  ``prefill()`` executes as a sequence of per-layer jitted calls carrying
  the hidden state; between layers the engine polls a preemption callback.
  An interrupted prefill keeps (hidden, layer index, KV-so-far) and resumes
  exactly where it stopped — tests assert bit-compatible logits vs an
  uninterrupted run. Prompts are padded to power-of-two buckets (masked via
  ``kv_lens``) so arbitrary lengths stop retracing the layer functions.
* **Mix decoding selection** (§3.4.4): each decode iteration builds its batch
  with ``core.scheduling.mix_decoding_selection`` under the TPOT SLO using
  the roofline perf model.

Decode batches are padded to bucket sizes (TPU/XLA static shapes, DESIGN §3).
Supported families here: dense + MoE with a single attention window (the
cluster-scale behaviour of every family is exercised via the simulator).

Engine hot path & attention backends
------------------------------------
The per-iteration hot path is allocation- and sync-free:

* ``backend="auto"|"pallas"|"interpret"|"ref"`` selects the attention
  implementation everywhere (prefill flash + paged decode attention).
  ``auto`` resolves to the Pallas TPU kernels when a TPU is attached and to
  the XLA/jnp reference path on CPU; ``interpret`` runs the Pallas kernel
  bodies on any backend (parity/debug). Threaded through ``CoLocatedServer``
  and ``launch.serve --backend``.
* ``k_pool``/``v_pool`` are **donated** through the jitted decode step and
  through the prefill KV scatter, so XLA writes the paged pools in place
  instead of copying the full (L, num_pages, page, Hkv*hd) arrays every
  iteration. Prefill buffers each layer's K/V and lands the whole prefill
  in a single donated scatter (one more at each preemption point).
* Sampling (greedy, or temperature/top-k via ``SamplingParams`` /
  ``set_sampling``) runs **inside** the jitted decode step — only the (B,)
  next-token ids cross the device boundary, never (B, vocab) logits.
* Per-layer parameters are pre-sliced once at construction; per-step token
  bookkeeping uses preallocated numpy rings (``TokenRing``), not Python
  lists.
* **Multi-step decode horizons**: ``decode_horizon(rids, K)`` fuses K
  consecutive decode iterations into ONE jitted dispatch — a
  ``jax.lax.scan`` over the same per-step core as ``decode_step``, with the
  sampled token fed back on-device, positions/lengths advanced inside the
  scan, pages claimed ahead so no request runs off its block table
  mid-horizon, and early-exit masking for rows that hit ``max_new_tokens``.
  One (K, B) token block crosses the device boundary per horizon instead of
  one (B,) sync per token; ``PerfModel.suggest_decode_horizon`` picks K.
* **Fused mixed horizons**: ``mixed_horizon(rids, prid, chunk_tokens, K)``
  runs K fused mixed iterations in one scan — each iteration lands a
  ``chunk_tokens / K`` sub-chunk slice of the pending prefill while
  decoding the residents, sharing ``_mixed_core`` with ``mixed_step`` so
  the per-step math is bit-identical. Pages for the whole chunk AND K
  decode tokens per resident are claimed before the dispatch;
  ``PerfModel.suggest_mixed_horizon`` picks K under the §3.4.1
  horizon-boundary preemption bound.

``benchmarks/bench_decode_hotpath.py`` measures steps/s and host overhead
per step and verifies pool donation from the lowered HLO;
``BENCH_engine.json`` records the baseline→after throughput trajectory.
"""
from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.perf_model import PerfModel
from repro.core.request import Phase, Request
from repro.core.scheduling import split_chunk
from repro.core.tracing import span, tracing
from repro.engine.kv_cache import (PagedKVCache, head_major_pages, pool_rows,
                                   token_major, transfer_checksum,
                                   verify_transfer)


class EngineCrashedError(RuntimeError):
    """Raised when a dispatch is attempted on a crashed engine."""
from repro.kernels import backend_flags, resolve_backend
from repro.kernels.paged_attention.ops import paged_attention
from repro.models import attention, layers, moe as moe_lib
from repro.models.attention import impl_for_backend
from repro.models.transformer import Transformer, _norm


@dataclass
class SamplingParams:
    """Engine-default sampling. ``temperature <= 0`` means greedy; ``top_k``
    0 keeps the full vocab. Per-request overrides via ``set_sampling``."""
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0


def sample_tokens(logits, key, temps, top_ks):
    """On-device sampler: greedy rows where temps <= 0, temperature/top-k
    elsewhere. logits (B, V) f32; temps (B,) f32; top_ks (B,) int32
    (0 = full vocab). Returns (B,) int32 token ids."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    srt = jnp.sort(logits, axis=-1)[:, ::-1]
    k_eff = jnp.clip(jnp.where(top_ks > 0, top_ks, V), 1, V)
    thresh = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    masked = jnp.where(logits >= thresh, logits, -jnp.inf)
    scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def pages_read(start: list[int], steps: list[int], page_size: int) -> int:
    """KV pages that decode rows read: row ``i`` runs ``steps[i]``
    iterations from position ``start[i]``, and the iteration at position
    ``p`` reads the ``p // page_size + 1`` pages that cover ``[0, p]``."""
    def upto(n):   # the sum over positions p < n of p // page_size + 1
        q, r = divmod(n, page_size)
        return page_size * q * (q + 1) // 2 + r * (q + 1)
    return sum(upto(s + n) - upto(s) for s, n in zip(start, steps))


class TokenRing:
    """Preallocated int32 token buffer (prompt + generated) with list-like
    reads. Appends write into preallocated storage (amortized O(1), no
    per-token Python list growth); capacity doubles if exceeded."""

    __slots__ = ("_buf", "_n")

    def __init__(self, tokens, capacity: int = 0):
        tokens = np.asarray(list(tokens), np.int32)
        cap = max(capacity, tokens.shape[0], 8)
        self._buf = np.empty(cap, np.int32)
        self._buf[: tokens.shape[0]] = tokens
        self._n = tokens.shape[0]

    def append(self, tok: int) -> None:
        if self._n == self._buf.shape[0]:
            grown = np.empty(self._buf.shape[0] * 2, np.int32)
            grown[: self._n] = self._buf
            self._buf = grown
        self._buf[self._n] = tok
        self._n += 1

    def tolist(self) -> list[int]:
        return self._buf[: self._n].tolist()

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return self._buf[: self._n][i].tolist()
        n = self._n
        if not -n <= i < n:
            raise IndexError(i)
        return int(self._buf[i % n if i < 0 else i])

    def __iter__(self):
        return iter(self.tolist())

    def __eq__(self, other) -> bool:
        if isinstance(other, TokenRing):
            return self.tolist() == other.tolist()
        if isinstance(other, (list, tuple)):
            return self.tolist() == list(other)
        return NotImplemented

    def __repr__(self) -> str:
        return f"TokenRing({self.tolist()})"


@dataclass
class PartialPrefill:
    """State of a layer-interrupted prefill (resume token). KV of completed
    layers is already flushed to the paged pool (one donated scatter per
    interruption segment)."""
    rid: int
    x: jnp.ndarray            # hidden after `layer` layers, (1, S, d)
    layer: int                # layers completed
    tokens: np.ndarray


@dataclass
class ChunkedPrefill:
    """State of a chunk-granular prefill: ``done`` prompt tokens have run
    through EVERY layer and their KV is landed in the paged pool, so a
    resume costs nothing but the next chunk — no layer re-execution
    (contrast ``PartialPrefill``, which holds a mid-stack hidden state)."""
    rid: int
    tokens: np.ndarray        # full prompt token ids
    done: int = 0             # tokens landed (all layers, KV in the pool)
    cached: int = 0           # leading tokens claimed from the prefix cache
                              # (block-aligned; counted in `done` but never
                              # computed here — aborts must not bill them)


@dataclass
class EngineStats:
    prefill_tokens: int = 0
    decode_tokens: int = 0
    preemptions: int = 0
    evictions: int = 0
    decode_steps: int = 0
    prefill_chunks: int = 0   # chunk-granular prefill dispatches
    mixed_steps: int = 0      # fused prefill-chunk + decode dispatches
    host_syncs: int = 0       # device->host syncs on the token path
                              # (one per dispatch that returns tokens)
    horizon_steps: int = 0    # decode iterations run inside K>1 horizons
    # decode occupancy, summed over decode iterations (K of a K-step
    # horizon): the iterations real rows ran against the rows padded to the
    # bucket, and the KV pages those real row-iterations read against the
    # pages the padded step fetches (bucket x the batch's page count padded
    # to a power of two); a row that finishes mid-horizon rides masked and
    # counts as padding from then on
    rows_dispatched: int = 0
    rows_padded: int = 0
    kv_pages_live: int = 0
    kv_pages_fetched: int = 0
    prefix_hits: int = 0      # prompts that claimed >= 1 cached prefix page
    cached_tokens: int = 0    # prompt tokens served from the prefix cache
    shared_pages: int = 0     # pages claimed via refcount bumps, cumulative
    # dispatch counts per kind — makes amortization observable directly
    # (e.g. mixed_horizon dispatches each cover K steps + K sub-chunks),
    # not just via the host_syncs aggregate
    dispatches_by_kind: dict = field(default_factory=lambda: {
        "prefill": 0, "decode": 0, "mixed": 0, "horizon": 0,
        "mixed_horizon": 0})


class ServingEngine:
    def __init__(self, model: Transformer, params, *, num_pages: int = 512,
                 page_size: int = 16, decode_buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32),
                 perf_model: PerfModel | None = None, backend: str = "auto",
                 sampling: SamplingParams | None = None,
                 prefix_cache: bool = False,
                 kernels_from: "ServingEngine | None" = None):
        cfg = model.cfg
        assert not cfg.local_global and not cfg.sliding_window, \
            "engine supports full-attention archs (cluster-scale behaviour of " \
            "windowed/SSM families is exercised via the simulator)"
        self.model = model
        self.cfg = cfg
        self.params = params
        self.backend = resolve_backend(backend)
        self.sampling = sampling or SamplingParams()
        # the KV pools live on the device that holds this engine's weights
        (device,) = jax.tree.leaves(params)[0].devices()
        self.cache = PagedKVCache(cfg, num_pages, page_size,
                                  enable_prefix_cache=prefix_cache,
                                  device=device)
        self.decode_buckets = tuple(sorted(decode_buckets))
        self.perf_model = perf_model
        self.requests: dict[int, Request] = {}
        self.token_buf: dict[int, TokenRing] = {}   # prompt + generated tokens
        self.partial: dict[int, PartialPrefill] = {}
        self.chunk_state: dict[int, ChunkedPrefill] = {}
        self.req_sampling: dict[int, tuple[float, int]] = {}
        # Length bucketing (padding + per-row kv_lens masking) needs dynamic
        # key masks: the XLA flash path honors them; the Pallas kernel's
        # kv_len is compile-time, so those backends keep exact shapes.
        self._prefill_bucketed = impl_for_backend(self.backend) == "xla"
        self.stats = EngineStats()
        if kernels_from is not None:
            # Pool runtimes run N+M engines over the same weights (one copy
            # per device); the jitted step functions only close over (model,
            # cfg, page_size, backend) and take the weights as arguments, so
            # sibling engines can share one compiled-kernel set instead of
            # re-tracing/compiling per engine.
            src = kernels_from
            assert (src.model is model
                    and src.cache.page_size == page_size
                    and src.backend == self.backend), \
                "kernel sharing requires identical model/page_size/backend"
            self._layer_fn = src._layer_fn
            self._embed_fn = src._embed_fn
            self._logits_fn = src._logits_fn
            self._sample_fn = src._sample_fn
            self._decode_fns = src._decode_fns
            self._mixed_fns = src._mixed_fns
            self._horizon_fns = src._horizon_fns
            self._mixed_horizon_fns = src._mixed_horizon_fns
        else:
            self._layer_fn = self._build_layer_fn()
            self._embed_fn = jax.jit(lambda p, t: model._embed(p, t))
            self._logits_fn = jax.jit(lambda p, x: model._logits(p, x))
            self._sample_fn = jax.jit(sample_tokens)
            self._decode_fns: dict[tuple[int, int], Callable] = {}
            self._mixed_fns: dict[tuple, Callable] = {}
            self._horizon_fns: dict[tuple, Callable] = {}
            self._mixed_horizon_fns: dict[tuple, Callable] = {}
        # per-layer weight slices for the legacy layer-granular prefill(),
        # built on its first call: a second device copy of every layer that
        # the chunked path never needs
        self._layer_params_cached: list | None = None
        self._base_key = jax.random.PRNGKey(self.sampling.seed)
        self._sample_step = 0
        self.alive = True

    # ------------------------------------------------------------------
    # fault surface
    # ------------------------------------------------------------------
    def crash(self) -> None:
        """Simulate an engine-process crash: device KV pools, block tables,
        and host-side request bookkeeping are all lost. Any further dispatch
        raises ``EngineCrashedError``. Recovery is the scheduler's job — the
        pool runtime re-admits every in-flight request from its frontend
        request log through the recompute path (greedy requests regenerate
        bit-identical token streams; see ``PoolRuntime._crash_engine``)."""
        self.alive = False
        self.requests.clear()
        self.token_buf.clear()
        self.partial.clear()
        self.chunk_state.clear()
        self.req_sampling.clear()
        self.cache.tables.clear()
        self.cache.lengths.clear()
        if self.cache.prefix is not None:
            # the radix tree indexes pool pages that no longer exist —
            # simply dropped; recovery recomputes (token parity holds)
            self.cache.prefix.clear()

    def _check_alive(self) -> None:
        if not self.alive:
            raise EngineCrashedError("engine has crashed; state is gone")

    # ------------------------------------------------------------------
    # sampling
    # ------------------------------------------------------------------
    def set_sampling(self, rid: int, temperature: float, top_k: int = 0) -> None:
        """Per-request override of the engine-default sampling params."""
        self.req_sampling[rid] = (temperature, top_k)

    def _sampling_arrays(self, rids: list[int], pad_to: int):
        d = (self.sampling.temperature, self.sampling.top_k)
        temps = np.zeros(pad_to, np.float32)
        topks = np.zeros(pad_to, np.int32)
        for i, r in enumerate(rids):
            temps[i], topks[i] = self.req_sampling.get(r, d)
        return temps, topks

    def _next_key(self):
        self._sample_step += 1
        return self._base_key, np.int32(self._sample_step)

    def _next_key_block(self, n: int):
        """Reserve ``n`` consecutive sample steps for a multi-step horizon.
        Returns (key, first_step); step t of the horizon folds in
        ``first_step + t`` — exactly the step ids n serial ``_next_key``
        calls would have produced, so K-step horizons sample bit-identically
        to K serial decode steps."""
        first = np.int32(self._sample_step + 1)
        self._sample_step += n
        return self._base_key, first

    # ------------------------------------------------------------------
    # layer-interruptible prefill
    # ------------------------------------------------------------------
    @staticmethod
    def pad_chunk(n: int) -> int:
        """Bucket a prefill prompt/chunk length to the next power of two
        (min 8) — bounds the jit trace count over arbitrary lengths the way
        ``pad_pages`` bounds the decode-table variants."""
        return max(8, 1 << (max(n, 1) - 1).bit_length())

    def _build_layer_fn(self):
        cfg = self.cfg
        impl = impl_for_backend(self.backend)

        @jax.jit
        def prefill_layer(lp, x, positions, kv_lens):
            h = _norm(cfg, lp["ln1"], x)
            a, (k, v) = attention.attn_prefill(
                lp["attn"], h, positions, cfg, window=cfg.sliding_window,
                kv_lens=kv_lens, impl=impl)
            if cfg.use_post_norm:
                a = _norm(cfg, lp["post_ln1"], a)
            x = x + a
            h = _norm(cfg, lp["ln2"], x)
            if cfg.is_moe:
                m, _ = moe_lib.moe_mlp(lp["moe"], h, cfg, groups=1)
            else:
                m = layers.mlp(lp["mlp"], h, cfg.mlp_act)
            if cfg.use_post_norm:
                m = _norm(cfg, lp["post_ln2"], m)
            return x + m, k, v

        return prefill_layer

    def _layer_params(self, i: int):
        if self._layer_params_cached is None:
            self._layer_params_cached = [
                jax.tree.map(lambda a, i=i: a[i], self.params["layers"])
                for i in range(self.cfg.num_layers)]
        return self._layer_params_cached[i]

    def add_request(self, req: Request, prompt_tokens: list[int]) -> None:
        self._check_alive()
        assert len(prompt_tokens) == req.prompt_len
        self.requests[req.rid] = req
        self.token_buf[req.rid] = TokenRing(
            prompt_tokens, capacity=req.prompt_len + req.output_len + 8)

    def _flush_prefill_kv(self, rid: int, start_layer: int, ks, vs) -> None:
        """Land buffered per-layer K/V in one donated scatter."""
        if ks:
            self.cache.write_prefill_layers(
                rid, start_layer, jnp.stack(ks), jnp.stack(vs))

    def prefill(self, rid: int, *, should_preempt: Callable[[], bool] | None = None,
                max_new_pages: bool = True) -> str:
        """Run (or resume) prefill for one request, checking the preemption
        callback between transformer layers. Returns "done" | "preempted"."""
        self._check_alive()
        # the legacy layer-granular path writes the WHOLE table via
        # write_prefill_layers — it must never run over a warm prefix claim
        # (that would overwrite pages shared with sibling requests)
        assert rid not in self.chunk_state, \
            "legacy prefill() cannot resume a chunked/warm-started request"
        req = self.requests[rid]
        cfg = self.cfg
        with span("ooco.engine.args"):
            if rid in self.partial:
                part = self.partial.pop(rid)
                x, start_layer, tokens = part.x, part.layer, part.tokens
            else:
                tokens = np.asarray(self.token_buf[rid][: req.prompt_len],
                                    np.int32)
                self.cache.ensure(rid, req.prompt_len)
                padded = tokens
                if self._prefill_bucketed:
                    # pad to a bucket length; the padded keys are masked out
                    # by kv_lens below, so one trace serves every length in
                    # the bucket instead of retracing per unique prompt length
                    padded = np.zeros(self.pad_chunk(tokens.shape[0]),
                                      np.int32)
                    padded[: tokens.shape[0]] = tokens
                x = self._embed_fn(self.params, jnp.asarray(padded)[None])
                start_layer = 0
            S = tokens.shape[0]
            positions = jnp.arange(x.shape[1])[None]
            kv_lens = jnp.asarray([S], jnp.int32)
        req.phase = Phase.PREFILLING
        ks, vs = [], []   # per-layer KV buffered; flushed once per segment
        with self._launch(self._layer_fn, "prefill_layer"):
            for li in range(start_layer, cfg.num_layers):
                x, k, v = self._layer_fn(self._layer_params(li), x,
                                         positions, kv_lens)
                ks.append(k[0, :S])
                vs.append(v[0, :S])
                req.prefill_layers_done = li + 1
                if (should_preempt is not None and li < cfg.num_layers - 1
                        and should_preempt()):
                    self._flush_prefill_kv(rid, start_layer, ks, vs)
                    self.partial[rid] = PartialPrefill(rid, x, li + 1, tokens)
                    self.stats.preemptions += 1
                    return "preempted"
            self._flush_prefill_kv(rid, start_layer, ks, vs)
            # first token from the last REAL hidden state, sampled on device
            logits = self._logits_fn(self.params, x[:, S - 1])
            temps, topks = self._sampling_arrays([rid], 1)
            if temps[0] > 0:
                key, step = self._next_key()
                nxt = self._sample_fn(logits, jax.random.fold_in(key, step),
                                      jnp.asarray(temps),
                                      jnp.asarray(topks))[0]
            else:
                nxt = jnp.argmax(logits, -1)[0]
        with span("ooco.engine.sync"):
            nxt = int(nxt)
        with span("ooco.engine.commit"):
            self.token_buf[rid].append(nxt)
            req.generated = 1
            req.phase = Phase.DECODING
            self.stats.prefill_tokens += S
            self.stats.host_syncs += 1
            self.stats.dispatches_by_kind["prefill"] += 1
        return "done"

    def abort_prefill(self, rid: int) -> None:
        """Discard partial prefill state — layer-granular (whole prompt is
        re-run later, the pessimistic legacy accounting) or chunk-granular
        (only the tokens actually landed count as recompute waste)."""
        part = self.partial.pop(rid, None)
        state = self.chunk_state.pop(rid, None)
        self.cache.free(rid)
        req = self.requests[rid]
        if state is not None:
            # cached tokens were claimed from the prefix tree, not computed
            # here — losing them wastes no FLOPs
            req.recompute_tokens += state.done - state.cached
        elif part is not None:
            req.recompute_tokens += req.prompt_len
        # neither: nothing was computed yet -> nothing wasted
        req.prefill_layers_done = 0
        req.prefill_tokens_done = 0
        req.cached_tokens = 0
        req.phase = Phase.QUEUED

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.decode_buckets:
            if n <= b:
                return b
        return self.decode_buckets[-1]

    @staticmethod
    def pad_pages(pages: int) -> int:
        """Pad a decode batch's page dimension to a power of two — bounds the
        set of (bucket, pages) jit variants. Shared with the benchmarks."""
        return 1 << (pages - 1).bit_length()

    @contextlib.contextmanager
    def _launch(self, fn, kind: str, *, rows: int = 0, bucket: int = 0,
                pages: int = 0, positions: np.ndarray | None = None,
                active: np.ndarray | None = None, steps: int = 1):
        """The ``ooco.engine.launch`` span around calls of the jitted step
        program ``fn`` (named ``kind``), after counting their decode
        occupancy over the ``steps`` iterations: the padded step runs
        ``bucket`` rows and fetches ``bucket * pages`` pages each
        iteration; of the ``rows`` real rows, row ``i`` runs ``active[i]``
        iterations (1 where ``active`` is None) from ``positions[i]`` and
        reads the pages that cover its positions."""
        n = [1] * rows if active is None else active[:rows].tolist()
        row_steps = sum(n)
        live = (pages_read(positions[:rows].tolist(), n, self.cache.page_size)
                if rows else 0)
        st = self.stats
        st.rows_dispatched += row_steps
        st.rows_padded += bucket * steps
        st.kv_pages_live += live
        st.kv_pages_fetched += bucket * pages * steps
        with span("ooco.engine.launch", kind=kind, bucket=bucket, rows=rows,
                  pages_padded=pages, K=steps, row_steps=row_steps,
                  pages_live=live) as s:
            compiled = fn._cache_size() if tracing() else None
            yield
            if compiled is not None:
                # 1 where the calls traced and compiled (or loaded) a program
                s.set_metadata(first_call=int(fn._cache_size() > compiled))

    def _decode_core(self):
        """One decode iteration over the layer stack — the computation
        SHARED by the plain jitted step and the K-step horizon scan, so the
        two paths are token-identical by construction. Returns
        ``core(params, tokens, positions, tables, lengths, page_ids, offs,
        k_pool, v_pool) -> (logits, k_pool, v_pool)``."""
        cfg = self.cfg
        model = self.model
        use_ref, interpret = backend_flags(self.backend)
        hd = cfg.head_dim_

        def core(params, tokens, positions, tables, lengths, page_ids, offs,
                 k_pool, v_pool):
            x = model._embed(params, tokens[:, None])

            # The pools ride in the scan CARRY (not xs/ys): per-layer writes
            # are dynamic-update-slices into the carried buffer, which XLA
            # keeps in place inside the loop and aliases to the donated
            # inputs — the xs/ys formulation forced a full-pool copy per
            # step because ys are always freshly stacked.
            def body(carry, inp):
                x, kpool, vpool = carry
                lp, li = inp
                h = _norm(cfg, lp["ln1"], x)
                k_new, v_new = attention.project_kv_for_cache(lp["attn"], h, positions, cfg)
                # round through cfg dtype, then store in the pool's storage
                # dtype (f32 on CPU — see PagedKVCache) for bit-parity with
                # the native-dtype pool layout
                kpool = kpool.at[li, page_ids, offs].set(
                    pool_rows(k_new[:, 0].astype(cfg.jnp_dtype), kpool))
                vpool = vpool.at[li, page_ids, offs].set(
                    pool_rows(v_new[:, 0].astype(cfg.jnp_dtype), vpool))
                q = layers.dense(lp["attn"]["wq"], h[:, 0]).reshape(
                    -1, cfg.num_heads, hd)
                if cfg.qk_norm:
                    q = layers.rmsnorm(lp["attn"]["q_norm"], q, cfg.norm_eps)
                q = layers.apply_rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
                comp_k, comp_v, local_tables = head_major_pages(
                    kpool, vpool, li, tables, cfg.num_kv_heads)
                a = paged_attention(q, comp_k, comp_v, local_tables, lengths,
                                    num_kv_heads=cfg.num_kv_heads,
                                    logit_softcap=cfg.attn_logit_softcap,
                                    use_ref=use_ref, interpret=interpret)
                a = layers.dense(lp["attn"]["wo"], a.reshape(a.shape[0], 1, -1))
                if cfg.use_post_norm:
                    a = _norm(cfg, lp["post_ln1"], a)
                x = x + a
                h = _norm(cfg, lp["ln2"], x)
                if cfg.is_moe:
                    m, _ = moe_lib.moe_mlp(lp["moe"], h, cfg, groups=1)
                else:
                    m = layers.mlp(lp["mlp"], h, cfg.mlp_act)
                if cfg.use_post_norm:
                    m = _norm(cfg, lp["post_ln2"], m)
                return (x + m, kpool, vpool), None

            (x, k_pool, v_pool), _ = jax.lax.scan(
                body, (x, k_pool, v_pool),
                (params["layers"], jnp.arange(cfg.num_layers)))
            return model._logits(params, x[:, 0]), k_pool, v_pool

        return core

    def _decode_fn(self, bucket: int, pages: int, sampled: bool = False):
        """``sampled=False`` specializes the step to plain argmax — the
        all-greedy default never pays the sampler's full-vocab sort."""
        key = (bucket, pages, sampled)
        if key in self._decode_fns:
            return self._decode_fns[key]
        core = self._decode_core()
        page_size = self.cache.page_size

        @functools.partial(jax.jit, donate_argnums=(5, 6))
        def decode_step(params, tokens, positions, tables, lengths, k_pool,
                        v_pool, key, sample_step, temps, top_ks):
            page_ids = jnp.take_along_axis(
                tables, (positions // page_size)[:, None], axis=1)[:, 0]
            offs = positions % page_size
            logits, k_pool, v_pool = core(params, tokens, positions, tables,
                                          lengths, page_ids, offs,
                                          k_pool, v_pool)
            if sampled:
                nxt = sample_tokens(logits, jax.random.fold_in(key, sample_step),
                                    temps, top_ks)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, k_pool, v_pool

        self._decode_fns[key] = decode_step
        return decode_step

    def _horizon_fn(self, bucket: int, pages: int, steps: int,
                    sampled: bool = False):
        """Jitted K-step decode horizon: ``jax.lax.scan`` over ``steps``
        consecutive decode iterations of the SAME per-step core as
        ``_decode_fn``, with the sampled token fed back on-device —
        positions/lengths advance inside the scan, both KV pools ride the
        donated carry, and the host sees only the stacked (K, B) token
        block. Rows whose ``active_steps`` budget is exhausted (request hit
        ``max_new_tokens`` mid-horizon, or bucket padding) are masked: their
        KV writes are redirected to the reserved trash page 0, their
        position freezes, and their carried token repeats — they can never
        corrupt live state or emit extra tokens."""
        fkey = (bucket, pages, steps, sampled)
        if fkey in self._horizon_fns:
            return self._horizon_fns[fkey]
        core = self._decode_core()
        page_size = self.cache.page_size

        @functools.partial(jax.jit, donate_argnums=(4, 5))
        def decode_horizon(params, tokens, positions, tables, k_pool, v_pool,
                           active_steps, key, first_step, temps, top_ks):
            def step_body(carry, t):
                tokens, positions, kpool, vpool = carry
                active = t < active_steps
                lengths = positions + 1
                page_ids = jnp.take_along_axis(
                    tables, (positions // page_size)[:, None], axis=1)[:, 0]
                page_ids = jnp.where(active, page_ids, 0)
                offs = positions % page_size
                logits, kpool, vpool = core(params, tokens, positions, tables,
                                            lengths, page_ids, offs,
                                            kpool, vpool)
                if sampled:
                    nxt = sample_tokens(
                        logits, jax.random.fold_in(key, first_step + t),
                        temps, top_ks)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, tokens)
                positions = jnp.where(active, positions + 1, positions)
                return (nxt, positions, kpool, vpool), nxt

            (tokens, positions, k_pool, v_pool), toks = jax.lax.scan(
                step_body, (tokens, positions, k_pool, v_pool),
                jnp.arange(steps, dtype=jnp.int32))
            return toks, k_pool, v_pool

        self._horizon_fns[fkey] = decode_horizon
        return decode_horizon

    def decode_step(self, rids: list[int]) -> dict[int, int]:
        """One continuous-batching decode iteration for the given requests;
        batches larger than the biggest bucket run as multiple bucket-sized
        chunks (no request is ever silently dropped). Returns rid -> new
        token for every rid passed."""
        self._check_alive()
        if not rids:
            return {}
        out: dict[int, int] = {}
        max_bucket = self.decode_buckets[-1]
        for i in range(0, len(rids), max_bucket):
            out.update(self._decode_chunk(rids[i: i + max_bucket]))
        return out

    def _decode_args(self, rids: list[int], claim_ahead: list[int] | None = None):
        """Build the padded device args of a decode batch (shared by the
        plain decode step, the fused mixed step, and the K-step horizon).

        ``claim_ahead`` (per-rid step counts) grows each block table to
        cover the horizon's writes at positions
        ``[context_len - 1, context_len - 1 + a)`` BEFORE the dispatch —
        the page claim-ahead; ``None`` is the single-step claim."""
        B = len(rids)
        bucket = self._bucket(B)
        for i, r in enumerate(rids):
            req = self.requests[r]
            self.cache.ensure(r, req.context_len if claim_ahead is None
                              else req.context_len - 1 + claim_ahead[i])
        pages = self.pad_pages(max(len(self.cache.tables[r]) for r in rids))
        tables = self.cache.batch_tables(rids, pad_to=pages)
        # the input token is the last one in the buffer; its position is
        # context_len - 1 and the cache covers [0, context_len) after writing
        positions = np.array([self.requests[r].context_len - 1 for r in rids], np.int32)
        tokens = np.array([self.token_buf[r][int(pos)] for r, pos in zip(rids, positions)],
                          np.int32)
        lengths = positions + 1
        pad = bucket - B
        if pad:
            tables = np.pad(tables, ((0, pad), (0, 0)))
            positions = np.pad(positions, (0, pad))
            tokens = np.pad(tokens, (0, pad))
            lengths = np.pad(lengths, (0, pad), constant_values=1)
        return bucket, pages, tokens, positions, tables, lengths

    def _decode_finish(self, rids: list[int], nxt: np.ndarray, dt: float) -> dict[int, int]:
        """Per-request bookkeeping after a decode (or fused) dispatch."""
        out = {}
        for i, r in enumerate(rids):
            req = self.requests[r]
            tok = int(nxt[i])
            self.token_buf[r].append(tok)
            req.generated += 1
            req.decode_time_sum += dt
            out[r] = tok
            if req.done:
                req.phase = Phase.FINISHED
                self.cache.free(r)
                self.req_sampling.pop(r, None)
        self.stats.decode_tokens += len(rids)
        self.stats.decode_steps += 1
        return out

    def _decode_chunk(self, rids: list[int]) -> dict[int, int]:
        t0 = time.perf_counter()
        with span("ooco.engine.args"):
            bucket, pages, tokens, positions, tables, lengths = \
                self._decode_args(rids)
            temps, topks = self._sampling_arrays(rids, bucket)
            sampled = (self.sampling.temperature > 0
                       or any(r in self.req_sampling for r in rids))
            fn = self._decode_fn(bucket, pages, sampled)
            key, sample_step = self._next_key()
        with self._launch(fn, "decode_step", rows=len(rids), bucket=bucket,
                          pages=pages, positions=positions):
            nxt_dev, self.cache.k_pool, self.cache.v_pool = fn(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables), jnp.asarray(lengths),
                self.cache.k_pool, self.cache.v_pool,
                key, sample_step, jnp.asarray(temps), jnp.asarray(topks))
        with span("ooco.engine.sync"):
            nxt = np.asarray(nxt_dev)   # (bucket,) ids — the only sync
        with span("ooco.engine.commit"):
            self.stats.host_syncs += 1
            self.stats.dispatches_by_kind["decode"] += 1
            return self._decode_finish(rids, nxt, time.perf_counter() - t0)

    # ------------------------------------------------------------------
    # multi-step decode horizons (K fused iterations, one host sync)
    # ------------------------------------------------------------------
    def max_horizon_for(self, rids: list[int], steps: int) -> int:
        """Largest horizon <= ``steps`` whose page claim-ahead fits the free
        pool (the claim is monotone in steps, and the K=1 claim is exactly
        what ``decode_step`` would take, so an admitted batch always gets at
        least 1)."""
        free = self.cache.available_pages

        def need(k: int) -> int:
            tot = 0
            for r in rids:
                req = self.requests[r]
                a = min(k, max(req.remaining, 1))
                tot += max(0, self.cache.pages_for(req.context_len - 1 + a)
                           - len(self.cache.tables.get(r, ())))
            return tot

        while steps > 1 and need(steps) > free:
            steps -= 1
        return max(steps, 1)

    def decode_horizon(self, rids: list[int], steps: int) -> dict[int, list[int]]:
        """Run up to ``steps`` consecutive decode iterations for ``rids`` as
        ONE jitted dispatch: the sampled token of step t feeds step t+1
        on-device, so only a (steps, B) token block crosses the device
        boundary — one host sync per horizon instead of one per token.
        Token-identical to ``steps`` serial ``decode_step`` calls (greedy
        and seeded sampling) for a fixed batch. Requests reaching
        ``max_new_tokens`` mid-horizon stop emitting (masked rows). Batches
        larger than the biggest bucket run as multiple bucket-sized
        horizons. Returns rid -> list of new tokens."""
        self._check_alive()
        if not rids:
            return {}
        steps = int(steps)
        if steps <= 1:
            return {r: [t] for r, t in self.decode_step(rids).items()}
        out: dict[int, list[int]] = {}
        max_bucket = self.decode_buckets[-1]
        for i in range(0, len(rids), max_bucket):
            out.update(self._horizon_chunk(rids[i: i + max_bucket], steps))
        return out

    def _horizon_chunk(self, rids: list[int], steps: int) -> dict[int, list[int]]:
        t0 = time.perf_counter()
        with span("ooco.engine.args"):
            ahead = [min(steps, self.requests[r].remaining) for r in rids]
            assert min(ahead) >= 1, "request already finished"
            bucket, pages, tokens, positions, tables, _ = \
                self._decode_args(rids, claim_ahead=ahead)
            active = np.zeros(bucket, np.int32)
            active[: len(rids)] = ahead
            temps, topks = self._sampling_arrays(rids, bucket)
            sampled = (self.sampling.temperature > 0
                       or any(r in self.req_sampling for r in rids))
            fn = self._horizon_fn(bucket, pages, steps, sampled)
            key, first_step = self._next_key_block(steps)
        with self._launch(fn, "decode_horizon", rows=len(rids),
                          bucket=bucket, pages=pages, positions=positions,
                          active=active, steps=steps):
            toks_dev, self.cache.k_pool, self.cache.v_pool = fn(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables), self.cache.k_pool, self.cache.v_pool,
                jnp.asarray(active), key, first_step,
                jnp.asarray(temps), jnp.asarray(topks))
        with span("ooco.engine.sync"):
            nxt = np.asarray(toks_dev)  # (steps, bucket) — the ONLY sync
        dt = time.perf_counter() - t0
        with span("ooco.engine.commit"):
            out, total = self._horizon_rows(rids, active, nxt, dt, steps)
            self.stats.decode_tokens += total
            self.stats.decode_steps += steps
            self.stats.horizon_steps += steps
            self.stats.host_syncs += 1
            self.stats.dispatches_by_kind["horizon"] += 1
        return out

    def _horizon_rows(self, rids: list[int], active: np.ndarray,
                      nxt: np.ndarray, dt: float, steps: int):
        """Per-request bookkeeping after a K-step horizon (decode or fused
        mixed): row ``i`` emitted ``nxt[:active[i], i]``. Returns (rid ->
        its new tokens, tokens emitted)."""
        out: dict[int, list[int]] = {}
        total = 0
        for i, r in enumerate(rids):
            req = self.requests[r]
            a = int(active[i])
            toks = [int(x) for x in nxt[:a, i]]
            buf = self.token_buf[r]
            for tok in toks:
                buf.append(tok)
            req.generated += a
            # the horizon's wall time amortizes over its steps; a row that
            # exits early is only charged for the steps it ran
            req.decode_time_sum += dt * a / steps
            total += a
            out[r] = toks
            if req.done:
                req.phase = Phase.FINISHED
                self.cache.free(r)
                self.req_sampling.pop(r, None)
        return out, total

    # ------------------------------------------------------------------
    # fused mixed prefill/decode step (chunked prefill)
    # ------------------------------------------------------------------
    def _mixed_core(self, dec_bucket: int, chunk_bucket: int,
                    chunk_pages: int):
        """One fused mixed iteration over the layer stack — the computation
        SHARED by the single-step jitted mixed step and the K-step
        mixed-horizon scan, so the two paths are token-identical by
        construction (the same way ``_decode_core`` backs both
        ``decode_step`` and ``decode_horizon``). Returns
        ``core(params, d_tokens, d_positions, d_tables, d_lengths, d_page,
        d_off, c_tokens, c_start, c_len, c_tables, k_pool, v_pool) ->
        (logits, k_pool, v_pool)`` where ``logits`` stacks the decode rows
        (dec_bucket, V) followed by the chunk's last-position row (1, V)."""
        cfg = self.cfg
        model = self.model
        page_size = self.cache.page_size
        use_ref, interpret = backend_flags(self.backend)
        with_decode = dec_bucket > 0
        hd = cfg.head_dim_

        def core(params, d_tokens, d_positions, d_tables, d_lengths,
                 d_page, d_off, c_tokens, c_start, c_len, c_tables,
                 k_pool, v_pool):
            xc = model._embed(params, c_tokens[None])            # (1, C, d)
            c_pos = c_start + jnp.arange(chunk_bucket, dtype=jnp.int32)
            in_chunk = jnp.arange(chunk_bucket) < c_len
            # padded chunk rows scatter into the reserved trash page 0
            # (exactly like padded decode rows) so they can never collide
            # with a real slot of the request's table
            c_page = jnp.where(
                in_chunk,
                c_tables[jnp.minimum(c_pos // page_size, chunk_pages - 1)],
                0)
            c_off = c_pos % page_size
            c_kv_len = (c_start + c_len)[None]                   # (1,)
            if with_decode:
                xd = model._embed(params, d_tokens[:, None])
            else:
                xd = jnp.zeros((), jnp.float32)  # carry placeholder

            def body(carry, inp):
                xd, xc, kpool, vpool = carry
                lp, li = inp
                # ---- KV writes land before either side's gather ----
                if with_decode:
                    hdn = _norm(cfg, lp["ln1"], xd)
                    k_new, v_new = attention.project_kv_for_cache(
                        lp["attn"], hdn, d_positions, cfg)
                    kpool = kpool.at[li, d_page, d_off].set(
                        pool_rows(k_new[:, 0].astype(cfg.jnp_dtype), kpool))
                    vpool = vpool.at[li, d_page, d_off].set(
                        pool_rows(v_new[:, 0].astype(cfg.jnp_dtype), vpool))
                hc = _norm(cfg, lp["ln1"], xc)
                qc, kc, vc = attention._project_qkv(
                    lp["attn"], hc, cfg, c_pos[None],
                    rope=not cfg.is_encoder_decoder)
                kpool = kpool.at[li, c_page, c_off].set(
                    pool_rows(kc[0].astype(cfg.jnp_dtype), kpool))
                vpool = vpool.at[li, c_page, c_off].set(
                    pool_rows(vc[0].astype(cfg.jnp_dtype), vpool))
                # ---- decode attention: backend paged kernel ----
                if with_decode:
                    q = layers.dense(lp["attn"]["wq"], hdn[:, 0]).reshape(
                        -1, cfg.num_heads, hd)
                    if cfg.qk_norm:
                        q = layers.rmsnorm(lp["attn"]["q_norm"], q, cfg.norm_eps)
                    q = layers.apply_rope(q[:, None], d_positions[:, None],
                                          cfg.rope_theta)[:, 0]
                    comp_k, comp_v, local = head_major_pages(
                        kpool, vpool, li, d_tables, cfg.num_kv_heads)
                    a = paged_attention(q, comp_k, comp_v, local, d_lengths,
                                        num_kv_heads=cfg.num_kv_heads,
                                        logit_softcap=cfg.attn_logit_softcap,
                                        use_ref=use_ref, interpret=interpret)
                    a = layers.dense(lp["attn"]["wo"], a.reshape(a.shape[0], 1, -1))
                    if cfg.use_post_norm:
                        a = _norm(cfg, lp["post_ln1"], a)
                    xd = xd + a
                    h2 = _norm(cfg, lp["ln2"], xd)
                    if cfg.is_moe:
                        m, _ = moe_lib.moe_mlp(lp["moe"], h2, cfg, groups=1)
                    else:
                        m = layers.mlp(lp["mlp"], h2, cfg.mlp_act)
                    if cfg.use_post_norm:
                        m = _norm(cfg, lp["post_ln2"], m)
                    xd = xd + m
                # ---- chunk attention over the request's landed pages ----
                ck = token_major(kpool[li, c_tables], cfg.num_kv_heads)[None]
                cv = token_major(vpool[li, c_tables], cfg.num_kv_heads)[None]
                ac = attention.flash_attention_xla(
                    qc, ck, cv, causal=True, q_offset=c_start,
                    kv_lens=c_kv_len, logit_softcap=cfg.attn_logit_softcap)
                ac = layers.dense(lp["attn"]["wo"],
                                  ac.reshape(1, chunk_bucket, -1))
                if cfg.use_post_norm:
                    ac = _norm(cfg, lp["post_ln1"], ac)
                xc = xc + ac
                hc2 = _norm(cfg, lp["ln2"], xc)
                if cfg.is_moe:
                    mc, _ = moe_lib.moe_mlp(lp["moe"], hc2, cfg, groups=1)
                else:
                    mc = layers.mlp(lp["mlp"], hc2, cfg.mlp_act)
                if cfg.use_post_norm:
                    mc = _norm(cfg, lp["post_ln2"], mc)
                return (xd, xc + mc, kpool, vpool), None

            (xd, xc, k_pool, v_pool), _ = jax.lax.scan(
                body, (xd, xc, k_pool, v_pool),
                (params["layers"], jnp.arange(cfg.num_layers)))
            # chunk next-token logits from the last REAL chunk position —
            # only meaningful (and only consumed) on the final chunk
            xlast = jax.lax.dynamic_slice_in_dim(
                xc, jnp.maximum(c_len - 1, 0), 1, axis=1)[:, 0]
            logits_c = model._logits(params, xlast)              # (1, V)
            if with_decode:
                logits = jnp.concatenate(
                    [model._logits(params, xd[:, 0]), logits_c], axis=0)
            else:
                logits = logits_c
            return logits, k_pool, v_pool

        return core

    def _mixed_fn(self, dec_bucket: int, dec_pages: int, chunk_bucket: int,
                  chunk_pages: int, sampled: bool = False):
        """Jitted fused step: one dispatch advances a token-budgeted prefill
        chunk AND decodes the resident batch, both writing the same donated
        KV pools. ``dec_bucket == 0`` specializes to a chunk-only step.

        The chunk is a length-bucketed query block at positions
        ``[start, start + c_len)``; its K/V is scattered into the paged pool
        first, then the chunk attends over the request's (gathered) pages —
        i.e. over everything already landed plus itself — with causal
        ``q_offset`` masking and a per-row ``kv_lens`` bound, so one trace
        serves every (chunk length, context) in the bucket."""
        fkey = (dec_bucket, dec_pages, chunk_bucket, chunk_pages, sampled)
        if fkey in self._mixed_fns:
            return self._mixed_fns[fkey]
        core = self._mixed_core(dec_bucket, chunk_bucket, chunk_pages)
        page_size = self.cache.page_size
        with_decode = dec_bucket > 0

        @functools.partial(jax.jit, donate_argnums=(8, 9))
        def mixed_step(params, d_tokens, d_positions, d_tables, d_lengths,
                       c_tokens, c_meta, c_tables, k_pool, v_pool,
                       key, sample_step, temps, top_ks):
            # c_meta (2,) int32 = [start (tokens already landed), c_len]
            if with_decode:
                d_page = jnp.take_along_axis(
                    d_tables, (d_positions // page_size)[:, None], axis=1)[:, 0]
                d_off = d_positions % page_size
            else:
                d_page = d_off = jnp.zeros(0, jnp.int32)
            logits, k_pool, v_pool = core(
                params, d_tokens, d_positions, d_tables, d_lengths,
                d_page, d_off, c_tokens, c_meta[0], c_meta[1], c_tables,
                k_pool, v_pool)
            if sampled:
                nxt = sample_tokens(logits, jax.random.fold_in(key, sample_step),
                                    temps, top_ks)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, k_pool, v_pool

        self._mixed_fns[fkey] = mixed_step
        return mixed_step

    def _mixed_horizon_fn(self, dec_bucket: int, dec_pages: int,
                          chunk_bucket: int, chunk_pages: int, steps: int,
                          sampled: bool = False):
        """Jitted K-step fused mixed horizon: ``jax.lax.scan`` over
        ``steps`` iterations of the SAME per-step core as ``_mixed_fn`` —
        each iteration lands one sub-chunk slice of the pending prefill
        chunk (``c_tokens``/``c_meta`` carry a per-iteration (steps, C)
        token block and (steps, 2) [start, len] metadata as scan xs) while
        running one decode iteration for the resident batch with the
        sampled token fed back on-device. Decode rows whose
        ``active_steps`` budget is exhausted (request hit
        ``max_new_tokens`` mid-horizon, or bucket padding) are masked
        exactly like ``_horizon_fn``: KV writes redirect to the reserved
        trash page 0, positions freeze, carried tokens repeat. Both KV
        pools ride the donated scan carry; the host sees only the stacked
        (steps, dec_bucket + 1) token block — one sync per horizon."""
        fkey = (dec_bucket, dec_pages, chunk_bucket, chunk_pages, steps,
                sampled)
        if fkey in self._mixed_horizon_fns:
            return self._mixed_horizon_fns[fkey]
        core = self._mixed_core(dec_bucket, chunk_bucket, chunk_pages)
        page_size = self.cache.page_size
        with_decode = dec_bucket > 0

        @functools.partial(jax.jit, donate_argnums=(4, 5))
        def mixed_horizon(params, d_tokens, d_positions, d_tables, k_pool,
                          v_pool, active_steps, c_tokens, c_meta, c_tables,
                          key, first_step, temps, top_ks):
            def step_body(carry, inp):
                d_toks, d_pos, kpool, vpool = carry
                t, c_tok, c_m = inp
                active = t < active_steps
                d_lengths = d_pos + 1
                if with_decode:
                    d_page = jnp.take_along_axis(
                        d_tables, (d_pos // page_size)[:, None], axis=1)[:, 0]
                    d_page = jnp.where(active, d_page, 0)
                    d_off = d_pos % page_size
                else:
                    d_page = d_off = jnp.zeros(0, jnp.int32)
                logits, kpool, vpool = core(
                    params, d_toks, d_pos, d_tables, d_lengths,
                    d_page, d_off, c_tok, c_m[0], c_m[1], c_tables,
                    kpool, vpool)
                if sampled:
                    nxt = sample_tokens(
                        logits, jax.random.fold_in(key, first_step + t),
                        temps, top_ks)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if with_decode:
                    d_toks = jnp.where(active, nxt[:dec_bucket], d_toks)
                    d_pos = jnp.where(active, d_pos + 1, d_pos)
                return (d_toks, d_pos, kpool, vpool), nxt

            (d_tokens, d_positions, k_pool, v_pool), toks = jax.lax.scan(
                step_body, (d_tokens, d_positions, k_pool, v_pool),
                (jnp.arange(steps, dtype=jnp.int32), c_tokens, c_meta))
            return toks, k_pool, v_pool

        self._mixed_horizon_fns[fkey] = mixed_horizon
        return mixed_horizon

    def mixed_step(self, decode_rids: list[int], prefill_rid: int | None = None,
                   chunk_tokens: int = 0) -> dict[int, int]:
        """One co-located iteration: decode ``decode_rids`` while advancing
        ``prefill_rid``'s chunk-granular prefill by up to ``chunk_tokens``
        prompt tokens, fused into a single dispatch when both sides are
        present. Either side may be empty (falls back to plain decode /
        chunk-only prefill). Returns rid -> new token for the decode rids;
        chunk progress is visible via ``prefill_progress`` and the request's
        phase flip to DECODING once the prompt completes."""
        self._check_alive()
        if prefill_rid is None or chunk_tokens <= 0:
            return self.decode_step(decode_rids)
        max_bucket = self.decode_buckets[-1]
        first = decode_rids[:max_bucket]
        out = self._mixed_dispatch(first, prefill_rid, chunk_tokens)
        for i in range(max_bucket, len(decode_rids), max_bucket):
            out.update(self._decode_chunk(decode_rids[i: i + max_bucket]))
        return out

    def prefill_progress(self, rid: int) -> int:
        """Prompt tokens landed so far by the chunked path (0 if none)."""
        state = self.chunk_state.get(rid)
        return state.done if state is not None else 0

    def claim_prefix(self, rid: int) -> int:
        """Match the request's prompt against the radix prefix cache and
        claim the hit by bumping page refcounts. Returns the matched token
        count (0 on miss / cache disabled). The match is capped at
        ``prompt_len - 1`` and rounded down to a page boundary, so the
        uncached suffix is >= 1 token and starts exactly on a fresh page:
        shared pages are never written — copy-on-write by construction.
        Chunked prefill then resumes at the match boundary."""
        if self.cache.prefix is None:
            return 0
        if rid in self.chunk_state or rid in self.cache.tables:
            return 0   # already started (warm or cold) — nothing to claim
        req = self.requests[rid]
        tokens = np.asarray(self.token_buf[rid][: req.prompt_len], np.int32)
        pages, matched = self.cache.prefix.match(
            tokens.tolist(), limit=req.prompt_len - 1)
        if matched == 0:
            return 0
        self.cache.adopt(rid, pages, matched)
        self.chunk_state[rid] = ChunkedPrefill(
            rid, tokens, done=matched, cached=matched)
        req.prefill_tokens_done = matched
        req.cached_tokens = matched
        self.stats.prefix_hits += 1
        self.stats.cached_tokens += matched
        self.stats.shared_pages += len(pages)
        return matched

    def _mixed_dispatch(self, rids: list[int], prid: int,
                        chunk_tokens: int) -> dict[int, int]:
        t0 = time.perf_counter()
        with span("ooco.engine.args"):
            req, state, c = self._chunk_state(prid, chunk_tokens)
            # pages are claimed chunk-by-chunk, so a preempted prefill only
            # ever holds capacity for what it has actually landed
            self.cache.ensure(prid, state.done + c)
            C = self.pad_chunk(c)
            c_tok = np.zeros(C, np.int32)
            c_tok[:c] = state.tokens[state.done: state.done + c]
            cp, c_tables = self._chunk_table(prid)
            c_meta = np.array([state.done, c], np.int32)
            if rids:
                bucket, pages, tokens, positions, tables, lengths = \
                    self._decode_args(rids)
            else:
                bucket = pages = 0
                tokens = positions = lengths = np.zeros(0, np.int32)
                tables = np.zeros((0, 0), np.int32)
            temps, topks = self._sampling_arrays(rids, bucket + 1)
            d = (self.sampling.temperature, self.sampling.top_k)
            temps[bucket], topks[bucket] = self.req_sampling.get(prid, d)
            sampled = (self.sampling.temperature > 0
                       or any(r in self.req_sampling for r in [*rids, prid]))
            fn = self._mixed_fn(bucket, pages, C, cp, sampled)
            key, sample_step = self._next_key()
        with self._launch(fn, "mixed_step", rows=len(rids), bucket=bucket,
                          pages=pages, positions=positions):
            nxt_dev, self.cache.k_pool, self.cache.v_pool = fn(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables), jnp.asarray(lengths),
                jnp.asarray(c_tok), jnp.asarray(c_meta),
                jnp.asarray(c_tables), self.cache.k_pool, self.cache.v_pool,
                key, sample_step, jnp.asarray(temps), jnp.asarray(topks))
        with span("ooco.engine.sync"):
            nxt = np.asarray(nxt_dev)   # (bucket + 1,) — single host sync
        dt = time.perf_counter() - t0
        with span("ooco.engine.commit"):
            self.stats.host_syncs += 1
            out = self._decode_finish(rids, nxt, dt) if rids else {}
            self.stats.prefill_chunks += 1
            self.stats.dispatches_by_kind["mixed" if rids else "prefill"] += 1
            if rids:
                self.stats.mixed_steps += 1
            self._chunk_landed(req, state, c, int(nxt[-1]))
        return out

    def _chunk_state(self, prid: int, chunk_tokens: int):
        """The request, its chunked-prefill state (claiming a cached prefix
        on first sight) and the chunk length ``c`` this dispatch lands."""
        req = self.requests[prid]
        state = self.chunk_state.get(prid)
        if state is None:
            assert prid not in self.partial, \
                "request already mid layer-granular prefill"
            # direct engine users reach the cache here; the cluster runtime
            # claims earlier (at admission) so planning sees residual work
            self.claim_prefix(prid)
            state = self.chunk_state.get(prid)
        if state is None:
            state = self.chunk_state[prid] = ChunkedPrefill(
                prid, np.asarray(self.token_buf[prid][: req.prompt_len],
                                 np.int32))
        c = min(int(chunk_tokens), req.prompt_len - state.done)
        assert c >= 1, "prefill already complete"
        req.phase = Phase.PREFILLING
        return req, state, c

    def _chunk_table(self, prid: int):
        """The prefilling request's block table padded to a power of two."""
        table = self.cache.tables[prid]
        cp = self.pad_pages(len(table))
        c_tables = np.zeros(cp, np.int32)
        c_tables[: len(table)] = table
        return cp, c_tables

    def _chunk_landed(self, req: Request, state: ChunkedPrefill, c: int,
                      first_token: int) -> None:
        """Bookkeeping after a dispatch landed ``c`` prompt tokens; when the
        prompt is complete, ``first_token`` (the chunk row's sample) is the
        first generated token."""
        prid = req.rid
        state.done += c
        req.prefill_tokens_done = state.done
        if state.done < req.prompt_len:
            return
        self.token_buf[prid].append(first_token)
        req.generated = 1
        req.phase = Phase.DECODING
        self.stats.prefill_tokens += req.prompt_len
        if self.cache.prefix is not None:
            # publish the full pages into the radix tree (refcount bump per
            # adopted page) so later prompts can reuse them; the partial
            # tail page stays private
            self.cache.prefix.insert(
                state.tokens.tolist(), self.cache.tables[prid])
        del self.chunk_state[prid]
        if req.done:   # one-output request: finished at prefill
            req.phase = Phase.FINISHED
            self.cache.free(prid)
            self.req_sampling.pop(prid, None)

    # ------------------------------------------------------------------
    # fused mixed-horizon dispatch (chunk + K decode iterations, one sync)
    # ------------------------------------------------------------------
    def max_mixed_horizon_for(self, rids: list[int], prid: int,
                              chunk_tokens: int, steps: int) -> int:
        """Largest horizon <= ``steps`` whose combined page claim-ahead —
        the FULL chunk for ``prid`` plus up to ``steps`` decode tokens per
        resident — fits the free pool. The chunk's claim is set aside
        first (it does not shrink with K: the whole chunk lands inside one
        horizon either way), then K shrinks like ``max_horizon_for``
        against the remainder, so neither side can starve the other into
        ``OutOfPagesError`` mid-scan."""
        req = self.requests[prid]
        done = req.prefill_tokens_done
        c = min(int(chunk_tokens), req.prompt_len - done)
        chunk_need = max(0, self.cache.pages_for(done + max(c, 1))
                         - len(self.cache.tables.get(prid, ())))
        free = self.cache.available_pages - chunk_need

        def need(k: int) -> int:
            tot = 0
            for r in rids:
                rq = self.requests[r]
                a = min(k, max(rq.remaining, 1))
                tot += max(0, self.cache.pages_for(rq.context_len - 1 + a)
                           - len(self.cache.tables.get(r, ())))
            return tot

        steps = min(int(steps), max(c, 1))
        while steps > 1 and need(steps) > free:
            steps -= 1
        return max(steps, 1)

    def mixed_horizon(self, decode_rids: list[int],
                      prefill_rid: int | None = None, chunk_tokens: int = 0,
                      steps: int = 1) -> dict[int, list[int]]:
        """Run up to ``steps`` fused mixed iterations as ONE jitted
        dispatch: per iteration a ``chunk_tokens / steps`` slice of
        ``prefill_rid``'s pending chunk lands in the donated KV pools while
        one decode iteration runs for ``decode_rids`` with on-device token
        feedback — K steps, one host sync. Token-identical to ``steps``
        serial ``mixed_step`` calls (greedy and seeded sampling for a
        fixed batch; rows hitting ``max_new_tokens`` mid-horizon stop
        emitting via masking). Falls back to ``decode_horizon`` when no
        chunk rides and to ``mixed_step`` when ``steps <= 1``. Decode rids
        beyond the biggest bucket run as plain decode horizons alongside.
        Returns rid -> list of new tokens for the decode rids; chunk
        progress is visible via ``prefill_progress`` and the phase flip to
        DECODING once the prompt completes."""
        self._check_alive()
        if prefill_rid is None or chunk_tokens <= 0:
            return self.decode_horizon(decode_rids, steps)
        steps = int(steps)
        if steps <= 1:
            return {r: [t] for r, t in self.mixed_step(
                decode_rids, prefill_rid, chunk_tokens).items()}
        max_bucket = self.decode_buckets[-1]
        out = self._mixed_horizon_dispatch(
            decode_rids[:max_bucket], prefill_rid, chunk_tokens, steps)
        rest = decode_rids[max_bucket:]
        if rest:
            out.update(self.decode_horizon(rest, steps))
        return out

    def _mixed_horizon_dispatch(self, rids: list[int], prid: int,
                                chunk_tokens: int,
                                steps: int) -> dict[int, list[int]]:
        t0 = time.perf_counter()
        req, state, c = self._chunk_state(prid, chunk_tokens)
        steps = min(steps, c)   # every sub-chunk must carry >= 1 token
        if steps <= 1:
            return {r: [t] for r, t in
                    self._mixed_dispatch(rids, prid, c).items()}
        with span("ooco.engine.args"):
            # the WHOLE horizon's chunk is claimed up front (claim-ahead to
            # the horizon end); sub-chunks land into it iteration by
            # iteration
            self.cache.ensure(prid, state.done + c)
            subs = split_chunk(c, steps)
            C = self.pad_chunk(max(subs))
            c_toks = np.zeros((steps, C), np.int32)
            c_meta = np.zeros((steps, 2), np.int32)
            pos = state.done
            for i, s in enumerate(subs):
                c_toks[i, :s] = state.tokens[pos: pos + s]
                c_meta[i] = (pos, s)
                pos += s
            cp, c_tables = self._chunk_table(prid)
            if rids:
                ahead = [min(steps, self.requests[r].remaining) for r in rids]
                assert min(ahead) >= 1, "request already finished"
                bucket, pages, tokens, positions, tables, _ = \
                    self._decode_args(rids, claim_ahead=ahead)
            else:
                ahead = []
                bucket = pages = 0
                tokens = positions = np.zeros(0, np.int32)
                tables = np.zeros((0, 0), np.int32)
            active = np.zeros(bucket, np.int32)
            active[: len(rids)] = ahead
            temps, topks = self._sampling_arrays(rids, bucket + 1)
            d = (self.sampling.temperature, self.sampling.top_k)
            temps[bucket], topks[bucket] = self.req_sampling.get(prid, d)
            sampled = (self.sampling.temperature > 0
                       or any(r in self.req_sampling for r in [*rids, prid]))
            fn = self._mixed_horizon_fn(bucket, pages, C, cp, steps, sampled)
            key, first_step = self._next_key_block(steps)
        with self._launch(fn, "mixed_horizon", rows=len(rids),
                          bucket=bucket, pages=pages, positions=positions,
                          active=active, steps=steps):
            toks_dev, self.cache.k_pool, self.cache.v_pool = fn(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                jnp.asarray(tables), self.cache.k_pool, self.cache.v_pool,
                jnp.asarray(active), jnp.asarray(c_toks),
                jnp.asarray(c_meta), jnp.asarray(c_tables), key, first_step,
                jnp.asarray(temps), jnp.asarray(topks))
        with span("ooco.engine.sync"):
            nxt = np.asarray(toks_dev)  # (steps, bucket + 1) — the ONLY sync
        dt = time.perf_counter() - t0
        with span("ooco.engine.commit"):
            self.stats.host_syncs += 1
            self.stats.dispatches_by_kind["mixed_horizon"] += 1
            out, total = self._horizon_rows(rids, active, nxt, dt, steps)
            self.stats.prefill_chunks += steps
            if rids:
                self.stats.decode_tokens += total
                self.stats.decode_steps += steps
                self.stats.horizon_steps += steps
                self.stats.mixed_steps += steps
            # sub-chunks all carry >= 1 token and sum to c, so the prompt
            # can only complete at the FINAL iteration — its chunk-row
            # sample is the first generated token
            self._chunk_landed(req, state, c, int(nxt[-1, bucket]))
        return out

    # ------------------------------------------------------------------
    def evict(self, rid: int) -> None:
        """Evict a decoding request (offline victim): free pages; it must
        re-prefill (recompute) later. Prefix-cache claims were a page-table
        update, not compute — like ``abort_prefill``/``_readmit``, losing
        them wastes no FLOPs, so only context beyond the claimed prefix
        counts as recompute."""
        req = self.requests[rid]
        req.recompute_tokens += max(req.context_len - req.cached_tokens, 0)
        req.evictions += 1
        req.phase = Phase.EVICTED
        self.cache.free(rid)
        self.stats.evictions += 1

    def release(self, rid: int) -> None:
        """Drop EVERY trace of a request from this engine — the cancel
        path. Idempotent and stage-agnostic: safe whether the request is
        mid-chunked-prefill, mid-legacy-prefill, decoding, already finished
        (pages freed by ``_decode_finish``), or unknown here. Unlike
        ``abort_prefill``/``evict`` it bills no recompute waste (a
        cancelled request will never re-run) and never raises on absent
        state, so the runtime can call it on every slot it might have
        touched. No-op on a crashed engine (its state is already gone)."""
        self.partial.pop(rid, None)
        self.chunk_state.pop(rid, None)
        self.req_sampling.pop(rid, None)
        self.requests.pop(rid, None)
        self.token_buf.pop(rid, None)
        if rid in self.cache.tables:
            self.cache.free(rid)
        else:
            self.cache.lengths.pop(rid, None)

    def migrate_out(self, rid: int):
        """Export KV for migration to another engine (RDMA->ICI analogue)."""
        k, v, n = self.cache.export_request(rid)
        self.cache.free(rid)
        return k, v, n

    def export_for_transfer(self, rid: int):
        """Export KV *without* freeing the source pages, plus an integrity
        checksum — the retry-safe transfer primitive: the source keeps its
        state until the destination has verified and imported the payload
        (``commit_transfer_out`` then releases it)."""
        k, v, n = self.cache.export_request(rid)
        return k, v, n, transfer_checksum(k, v)

    def commit_transfer_out(self, rid: int) -> None:
        """Release a request's local state after a verified transfer."""
        self.cache.free(rid)
        self.requests.pop(rid, None)
        self.token_buf.pop(rid, None)

    def migrate_in(self, rid: int, req: Request, tokens, k, v, n: int,
                   sampling: tuple[float, int] | None = None,
                   checksum: float | None = None) -> None:
        self._check_alive()
        if checksum is not None:
            # raises TransferIntegrityError BEFORE any state lands here —
            # a corrupt payload leaves the destination untouched so the
            # source can simply re-send
            verify_transfer(k, v, checksum)
        self.requests[rid] = req
        toks = list(tokens)
        self.token_buf[rid] = TokenRing(
            toks, capacity=len(toks) + max(req.remaining, 0) + 8)
        if sampling is not None:
            self.req_sampling[rid] = sampling
        self.cache.import_request(rid, k, v, n)
        req.phase = Phase.DECODING
