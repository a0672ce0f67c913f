"""Serving engines: fixed-batch prefill+decode, and paged continuous batching.

Two engines share the request surface (DESIGN.md §8):

* :class:`Engine` serves fixed-shape batches (one compiled prefill and one
  compiled decode_step per (batch, prompt_len) bucket). Each bucket pins the
  KernelPolicy set its compiled functions resolve to — the autotuner's
  per-shape-bucket memoization means the pinned policy and the policy the
  kernels trace with are the same object (DESIGN.md §5), so the report in
  :attr:`Engine.bucket_policies` is exact. Compiled buckets are held in an
  LRU capped by ``max_cached_buckets``: evicting a bucket drops its jitted
  callables (and with them the compiled executables), so a long-lived engine
  serving many shapes stays bounded.
* :class:`PagedEngine` runs continuous batching over the paged KV cache
  (``serve.kv_cache``): new requests are admitted into free batch slots
  each step (single-sequence prefill into freshly allocated pages), finished
  ones retire (pages freed) without disturbing their neighbours, and the
  one compiled decode step serves every slot regardless of its length.
  Decode policies are pinned per (batch_slots, page_count) bucket: the page
  table is sliced to the smallest power-of-two page count covering the
  active slots, so short-context phases run a smaller split-KV grid.

``RequestQueue`` is the continuous-batching-lite layer over :class:`Engine`:
requests are bucketed by padded prompt length and flushed as full batches.
"""
from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import autotune
from . import kv_cache as kvc


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, prompt + generated)
    prompt_len: int
    steps: int


def _lru_get(lru: collections.OrderedDict, key, build, cap: int,
             stats: dict | None = None):
    """Get-or-build with LRU eviction — evicted entries drop their jitted
    callables (and compiled executables) with them. ``stats`` (an engine's
    hits/misses/evictions dict) is also mirrored into the telemetry
    counters when a capture is active."""
    entry = lru.get(key)
    if entry is None:
        if stats is not None:
            stats["misses"] += 1
        obs.incr("engine.bucket_lru.misses")
        entry = build()
        lru[key] = entry
        while len(lru) > cap:
            lru.popitem(last=False)
            if stats is not None:
                stats["evictions"] += 1
            obs.incr("engine.bucket_lru.evictions")
    else:
        lru.move_to_end(key)
        if stats is not None:
            stats["hits"] += 1
        obs.incr("engine.bucket_lru.hits")
    return entry


class Engine:
    def __init__(self, model, params, *, max_len: int = 4096, mesh=None,
                 donate_cache: bool = True, max_cached_buckets: int = 8,
                 pretuned=None):
        if pretuned is not None:
            # install the calibrated table (path or report dict) before any
            # bucket pins, so every pinned policy set sees it
            autotune.use_pretuned(pretuned)
        self.model = model
        self.params = params
        self.max_len = max_len
        self.mesh = mesh
        self.donate_cache = donate_cache
        self.max_cached_buckets = max_cached_buckets
        # ONE LRU for every compiled-fn kind, under one shared cap:
        # (batch, prompt_len) -> {policies, prefill} and ("decode", batch)
        # -> {policies, decode}. The decode step's traced shapes depend
        # only on batch (token (B,1), max_len cache), so it gets its own
        # key kind rather than a per-prompt-length recompile — but it
        # competes for the same cap as the prefill buckets, so a long tail
        # of prompt lengths can no longer bloat the cache past the cap.
        self._buckets: collections.OrderedDict = collections.OrderedDict()
        self.lru_stats = {"hits": 0, "misses": 0, "evictions": 0}

    @property
    def bucket_policies(self) -> dict:
        """{key: {op: KernelPolicy}} of the live buckets — prefill keys are
        (batch, prompt_len), decode keys are ("decode", batch)."""
        return {k: e["policies"] for k, e in self._buckets.items()}

    def _bucket(self, batch: int, prompt_len: int) -> dict:
        """Resolve-or-evict the compiled bucket for (batch, prompt_len)."""
        model = self.model

        def build():
            return {
                "policies": autotune.policies_for_model(
                    model.cfg, batch=batch, seq_len=prompt_len,
                    decode_len=self.max_len),
                "prefill": jax.jit(
                    lambda params, batch_, cache: model.prefill(
                        params, batch_, cache)),
            }
        return _lru_get(self._buckets, (batch, prompt_len), build,
                        self.max_cached_buckets, self.lru_stats)

    def _decode_fn(self, batch: int):
        model, cfg = self.model, self.model.cfg

        def build():
            from repro.kernels.attention import resolve_decode_policy
            hkv = cfg.num_kv_heads
            return {
                "policies": {"attention_decode": resolve_decode_policy(
                    batch, hkv, cfg.num_heads // hkv, self.max_len,
                    cfg.head_dim, cfg.compute_dtype)},
                "decode": jax.jit(
                    lambda params, tok, cache, pos: model.decode_step(
                        params, tok, cache, pos),
                    donate_argnums=(2,) if self.donate_cache else ()),
            }
        return _lru_get(self._buckets, ("decode", batch), build,
                        self.max_cached_buckets, self.lru_stats)["decode"]

    def _sample(self, logits, temperature: float, rng):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        return jax.random.categorical(rng, logits / temperature, axis=-1)

    def generate(self, prompts, max_new_tokens: int, *,
                 temperature: float = 0.0, rng=None,
                 extra_batch: Optional[dict] = None) -> GenerationResult:
        """prompts: (B, S) int32. Greedy (T=0) or temperature sampling."""
        prompts = jnp.asarray(prompts, jnp.int32)
        b, s = prompts.shape
        entry = self._bucket(b, s)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        cache = self.model.init_cache(b, self.max_len)
        with obs.span("engine.prefill", batch=b, prompt_len=s):
            if self.model.cfg.family == "encdec":
                batch = dict(extra_batch or {}, inputs=prompts)
                cache, logits = entry["prefill"](self.params, batch, cache)
            else:
                cache, logits = entry["prefill"](self.params, prompts, cache)
        toks = [prompts]
        rngs = jax.random.split(rng, max_new_tokens)
        decode = self._decode_fn(b)
        next_tok = self._sample(logits, temperature, rngs[0])[:, None]
        with obs.span("engine.decode", batch=b, tokens=max_new_tokens):
            for i in range(max_new_tokens):
                toks.append(next_tok)
                if i == max_new_tokens - 1:
                    break
                cache, logits = decode(self.params, next_tok, cache, s + i)
                next_tok = self._sample(logits, temperature,
                                        rngs[i + 1])[:, None]
        out = np.asarray(jnp.concatenate(toks, axis=1))
        return GenerationResult(out, s, max_new_tokens)


@dataclasses.dataclass
class Request:
    """One generation request.

    Sampling contract (docs/serving.md): ``temperature=None`` inherits the
    engine's default; 0.0 is greedy argmax — bitwise deterministic, no rng
    consumed. For temperature > 0, ``seed`` pins a per-request PRNG stream:
    :class:`PagedEngine` folds the sequence's absolute position into
    ``PRNGKey(seed)`` per emitted token, so the draw is independent of
    batch composition and admission order. Unseeded sampled requests draw
    from the engine's shared stream (reproducible per engine ``rng`` but
    schedule-dependent). :class:`RequestQueue` batches share one stream
    seeded by the batch's first seeded request.
    """
    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    temperature: Optional[float] = None      # None = engine default
    seed: Optional[int] = None


class RequestQueue:
    """Continuous-batching-lite: bucket by padded length, flush full batches."""

    def __init__(self, engine: Engine, batch_size: int,
                 buckets=(128, 512, 2048)):
        self.engine = engine
        self.batch_size = batch_size
        self.buckets = sorted(buckets)
        self.pending: dict[int, list[Request]] = {b: [] for b in self.buckets}
        self.results: dict[int, np.ndarray] = {}

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket")

    def submit(self, req: Request) -> None:
        self.pending[self._bucket(len(req.prompt))].append(req)

    @property
    def engine_temperature(self) -> float:
        """The engine's default temperature (dense Engine: greedy)."""
        return getattr(self.engine, "temperature", 0.0)

    def flush(self, *, force: bool = False) -> int:
        """Serve full (or, with ``force``, padded partial) batches.

        Returns the number of *real* requests served — padding duplicates of
        the last request (which fill out a forced partial batch to the
        compiled batch size) are not counted. A resubmitted uid overwrites
        its previous result with a warning rather than being silently
        dropped.
        """
        served = 0
        for bucket, reqs in self.pending.items():
            # partition by effective temperature (order-preserving): one
            # compiled batch shares one sampling config, so mixing greedy
            # and sampled requests in a batch would silently ignore the
            # per-request temperature (the bug this plumbing fixes)
            by_temp: dict = {}
            for r in reqs:
                t = (r.temperature if r.temperature is not None
                     else self.engine_temperature)
                by_temp.setdefault(t, []).append(r)
            reqs[:] = []
            for temp, treqs in by_temp.items():
                while len(treqs) >= self.batch_size or (force and treqs):
                    group = treqs[: self.batch_size]
                    del treqs[: self.batch_size]
                    served += self._serve_batch(bucket, group, temp)
                reqs.extend(treqs)            # leftovers wait for more
        return served

    def _serve_batch(self, bucket: int, group: list, temperature: float
                     ) -> int:
        n_real = len(group)
        while len(group) < self.batch_size:   # pad the last batch
            group.append(group[-1])
        prompts = np.stack([
            np.pad(r.prompt, (bucket - len(r.prompt), 0))
            for r in group])
        max_new = max(r.max_new_tokens for r in group)
        seeds = [r.seed for r in group[:n_real] if r.seed is not None]
        rng = jax.random.PRNGKey(seeds[0]) if seeds else None
        result = self.engine.generate(prompts, max_new,
                                      temperature=temperature, rng=rng)
        for r, row in zip(group[:n_real], result.tokens[:n_real]):
            if r.uid in self.results:
                warnings.warn(
                    f"RequestQueue: duplicate uid {r.uid} — "
                    "overwriting previous result", stacklevel=2)
            self.results[r.uid] = row[bucket - len(r.prompt):]
        return n_real


# ---------------------------------------------------------------------------
# Continuous batching over the paged KV cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Slot:
    """Host-side record of one active batch slot."""
    req: Request
    n_pages: int                 # pages currently backing the sequence
    generated: list              # sampled token ids (ints)
    next_token: int              # token to feed at the next decode step
    pages: list = dataclasses.field(default_factory=list)
    # next prompt position to prefill; -1 once prefill is complete. A slot
    # mid-prefill is masked out of the shared decode step (its page-table
    # row and length are zeroed for that launch) so decode appends cannot
    # scribble over pages the chunk loop is still filling.
    prefill_cursor: int = -1

    @property
    def prefilling(self) -> bool:
        return self.prefill_cursor >= 0


def _pow2(x: int) -> int:
    return 1 << max(0, (x - 1).bit_length())


def _program(fn: Callable, name: str) -> Callable:
    """``fn`` under a stable name: its jitted program is then the module
    ``jit_<name>`` of a device trace, not ``jit__lambda``."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program


class PagedEngine:
    """Continuous batching: paged KV cache + one compiled decode step.

    Admission: each :meth:`step` first moves pending requests into free
    batch slots while the allocator can cover their prompt pages (the
    prefill runs at the exact prompt length, compiled once per length —
    padding the tokens would contaminate recurrent-layer state). Decode:
    one compiled ``decode_step_paged`` serves every slot; the page table is
    sliced to the pinned (batch_slots, page_count) bucket so short-context
    phases run a smaller split-KV grid. Growth: a slot crossing a page
    boundary gets its next page just-in-time; if the pool is exhausted the
    youngest stalled slot is preempted (recompute policy — its pages are
    freed and a continuation request rejoins the queue front). Retirement:
    a slot that reaches ``max_new_tokens`` frees its pages and its result
    appears in :attr:`results` — its neighbours never notice.

    Serving fast paths (DESIGN.md §14, all opt-in; defaults reproduce the
    plain engine bitwise):
      * ``prefix_cache=True`` — full KV pages of completed prompts are kept
        in a refcounted trie; later prompts sharing a page-aligned prefix
        skip its prefill and share the physical pages.
      * ``chunk_tokens=C`` — prompts prefill in fixed C-token chunks, one
        per step, interleaved with decode (the mid-prefill slot is masked
        out of the shared decode launch), bounding decode stall per step.
      * ``draft_model=... , spec_tokens=k`` — greedy speculative decoding:
        the draft proposes k-1 tokens, the target verifies them in a single
        k-token decode, and each round emits 1..k tokens per sequence.
    """

    def __init__(self, model, params, *, batch_slots: int = 4,
                 page_size: int = 64, max_pages_per_seq: int = 8,
                 n_pages: Optional[int] = None, temperature: float = 0.0,
                 rng=None, max_cached_buckets: int = 8,
                 prefix_cache: bool = False,
                 chunk_tokens: Optional[int] = None,
                 draft_model=None, draft_params=None, spec_tokens: int = 0,
                 pretuned=None, logits_hook: Optional[Callable] = None):
        if pretuned is not None:
            # calibrated policy table (path or report dict), installed
            # before the first page-count bucket pins its split-KV policy
            autotune.use_pretuned(pretuned)
        if model.init_paged_cache is None:
            raise ValueError(
                f"{model.cfg.name}: no paged decode surface (decoder-only "
                "LM/VLM backbones only)")
        self.model = model
        self.params = params
        self.batch_slots = batch_slots
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        # +1: physical page 0 is the reserved null page
        self.n_pages = (n_pages if n_pages is not None
                        else batch_slots * max_pages_per_seq + 1)
        self.temperature = temperature
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.max_cached_buckets = max_cached_buckets

        # ---- serving fast paths (DESIGN.md §14; all off by default —
        # defaults reproduce the exact-length one-shot engine bitwise) ----
        attn_only = all(model.cfg.layer_kind(i) in ("attn", "local", "moe")
                        for i in range(model.cfg.num_layers))
        if prefix_cache and not attn_only:
            raise ValueError(
                "prefix caching shares position-addressable KV pages; "
                f"{model.cfg.name} has recurrent layers")
        if chunk_tokens is not None:
            if not attn_only:
                raise ValueError(
                    "chunked prefill re-enters the prompt mid-stream; "
                    f"{model.cfg.name}'s recurrent state cannot")
            if chunk_tokens <= 0 or chunk_tokens % page_size:
                raise ValueError(
                    f"chunk_tokens={chunk_tokens} must be a positive "
                    f"multiple of page_size={page_size}")
        self.prefix = kvc.PrefixCache(page_size) if prefix_cache else None
        self.chunk_tokens = chunk_tokens
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.spec_tokens = spec_tokens
        if draft_model is not None:
            if spec_tokens < 2:
                raise ValueError("speculative decoding needs spec_tokens"
                                 " >= 2 (1 draft + 1 correction minimum)")
            if not attn_only:
                raise ValueError("speculative verify needs an attention-"
                                 f"only stack; {model.cfg.name} is hybrid")
            if temperature != 0.0:
                raise ValueError(
                    "speculative decoding acceptance is defined for greedy "
                    "sampling (temperature=0.0) in this engine")
            if draft_model.cfg.vocab_size != model.cfg.vocab_size:
                raise ValueError("draft and target must share a vocabulary")
            if logits_hook is not None:
                raise ValueError("logits_hook observes single-token steps; "
                                 "speculative rounds emit several at once")
        self._spec = draft_model is not None
        self.logits_hook = logits_hook

        self.cache = model.init_paged_cache(batch_slots, self.n_pages,
                                            page_size)
        self.draft_cache = (draft_model.init_paged_cache(
            batch_slots, self.n_pages, page_size) if self._spec else None)
        self.alloc = kvc.PageAllocator(self.n_pages)
        self.state = kvc.init_page_state(batch_slots, max_pages_per_seq)
        self.slots: dict[int, _Slot] = {}       # slot id -> active record
        self.pending: collections.deque = collections.deque()
        self.results: dict[int, np.ndarray] = {}
        self.steps = 0
        self.preemptions = 0
        self.admissions = 0
        self.tokens_generated = 0
        # paged-decode blocks the kernel walks / the launch grid holds
        self.kv_blocks_walked = 0
        self.kv_blocks_in_grid = 0
        self.chunks_prefilled = 0
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_participations = 0    # (slot, round) pairs
        self.peak_pages_in_use = 0
        # uid -> (start of its running phase, is it a preempted
        # continuation's); kept only while a capture is active
        self._phase_start: dict[int, tuple[float, bool]] = {}
        self.lru_stats = {"hits": 0, "misses": 0, "evictions": 0}
        # One LRU, one cap, many key kinds: (batch_slots, page_count) ->
        # decode, ("prefill", S) -> exact prefill, ("chunk", C) -> chunked/
        # suffix prefill, ("verify", page_count) -> k-token verify, and
        # "draft_*" twins of each for the speculative draft model.
        # Compiled fns are evicted with their entry.
        self._buckets: collections.OrderedDict = collections.OrderedDict()

    # -- bucket pinning ----------------------------------------------------
    @property
    def bucket_policies(self) -> dict:
        return {k: e["policies"] for k, e in self._buckets.items()}

    def _touch(self, key, build) -> dict:
        return _lru_get(self._buckets, key, build, self.max_cached_buckets,
                        self.lru_stats)

    def _note_occupancy(self) -> None:
        used = self.n_pages - 1 - self.alloc.free_pages
        if used > self.peak_pages_in_use:
            self.peak_pages_in_use = used
        obs.gauge("engine.peak_pages_in_use", used)

    def _decode_bucket(self, mp_bucket: int, *, draft: bool = False) -> dict:
        """Compiled decode + pinned split-KV policy for a page-count bucket."""
        from repro.kernels.attention import resolve_decode_policy
        model = self.draft_model if draft else self.model
        cfg = model.cfg
        prefix = "draft_" if draft else ""

        def build():
            hkv = cfg.num_kv_heads
            policy = resolve_decode_policy(
                self.batch_slots, hkv, cfg.num_heads // hkv,
                mp_bucket * self.page_size, cfg.head_dim, cfg.compute_dtype,
                page_size=self.page_size)
            return {
                "policies": {"attention_decode": policy},
                "decode": jax.jit(
                    _program(model.decode_step_paged,
                             prefix + "decode_step_paged"),
                    donate_argnums=(2,)),   # pools are the dominant buffers
            }
        key = (("draft_decode", mp_bucket) if draft
               else (self.batch_slots, mp_bucket))
        return self._touch(key, build)

    def _prefill_bucket(self, padded_len: int, *, draft: bool = False
                        ) -> dict:
        model = self.draft_model if draft else self.model
        prefix = "draft_" if draft else ""

        def build():
            return {
                "policies": autotune.policies_for_model(
                    model.cfg, batch=1, seq_len=padded_len,
                    decode_len=self.max_pages_per_seq * self.page_size),
                "prefill": jax.jit(
                    _program(model.prefill_paged, prefix + "prefill_paged"),
                    donate_argnums=(2,)),
            }
        key = ("draft_prefill" if draft else "prefill", padded_len)
        return self._touch(key, build)

    def _chunk_bucket(self, chunk_len: int, *, draft: bool = False) -> dict:
        """Compiled chunk/suffix prefill: ONE instance per chunk length
        serves every chunk index and every prefix-match offset (``start``
        and ``last_index`` are traced operands, not trace constants)."""
        from repro.kernels.attention import resolve_decode_policy
        model = self.draft_model if draft else self.model
        cfg = model.cfg
        prefix = "draft_" if draft else ""

        def build():
            hkv = cfg.num_kv_heads
            policy = resolve_decode_policy(
                1, hkv, cfg.num_heads // hkv,
                self.max_pages_per_seq * self.page_size, cfg.head_dim,
                cfg.compute_dtype, page_size=self.page_size,
                q_tokens=chunk_len)
            return {
                "policies": {"attention_decode": policy},
                "chunk": jax.jit(
                    _program(model.prefill_paged_chunk,
                             prefix + "prefill_paged_chunk"),
                    donate_argnums=(2,)),
            }
        key = ("draft_chunk" if draft else "chunk", chunk_len)
        return self._touch(key, build)

    def _verify_bucket(self, mp_bucket: int) -> dict:
        """Compiled k-token verify step (the speculative target pass)."""
        from repro.kernels.attention import resolve_decode_policy
        model, cfg = self.model, self.model.cfg

        def build():
            hkv = cfg.num_kv_heads
            policy = resolve_decode_policy(
                self.batch_slots, hkv, cfg.num_heads // hkv,
                mp_bucket * self.page_size, cfg.head_dim, cfg.compute_dtype,
                page_size=self.page_size, q_tokens=self.spec_tokens)
            return {
                "policies": {"attention_decode": policy},
                "verify": jax.jit(
                    _program(model.decode_step_paged, "verify_step_paged"),
                    donate_argnums=(2,)),
            }
        return self._touch(("verify", mp_bucket), build)

    # -- request lifecycle -------------------------------------------------
    def submit(self, req: Request) -> None:
        total = len(req.prompt) + req.max_new_tokens
        if self._spec:
            # a verify round may overshoot the budget by up to
            # spec_tokens - 1 stale positions before retirement truncates
            total += self.spec_tokens
        cap = min(self.max_pages_per_seq, self.n_pages - 1) * self.page_size
        if total > cap:
            raise ValueError(
                f"request {req.uid}: {total} tokens exceed per-sequence "
                f"capacity {cap} (max_pages_per_seq * page_size)")
        if self._spec and (req.temperature not in (None, 0.0)):
            raise ValueError(
                f"request {req.uid}: speculative decoding requires greedy "
                "requests (temperature 0.0)")
        self.pending.append(req)
        self._request_phase(req.uid, None)

    def _request_phase(self, uid: int, ended: Optional[str], *,
                       reopen: bool = True, preempted: bool = False) -> None:
        """Telemetry of a request's phases (queue, prefill, decode): close
        the running one as the interval ``engine.request.<ended>`` and open
        the next now. A preempted continuation's phases carry
        ``preempted=True``."""
        if not obs.enabled():
            return
        now = time.perf_counter()
        t0, flagged = self._phase_start.pop(uid, (None, False))
        if ended is not None and t0 is not None:
            obs.interval(f"engine.request.{ended}", t0, now, rid=uid,
                         **({"preempted": True} if flagged else {}))
        if reopen:
            self._phase_start[uid] = (now, flagged or preempted)

    def _effective_temperature(self, req: Request) -> float:
        return self.temperature if req.temperature is None else req.temperature

    def _sample_slot(self, logits_row, req: Request, position: int) -> int:
        """Sample one token for one sequence (docs/serving.md contract).

        ``position`` is the token's absolute sequence position — the
        fold_in index for seeded requests, so the draw is invariant to
        batch composition, admission order, and recompute preemption.
        """
        if self.logits_hook is not None:
            self.logits_hook(req.uid, position, logits_row)
        t = self._effective_temperature(req)
        if t == 0.0:
            return int(jnp.argmax(logits_row))
        if req.seed is not None:
            key = jax.random.fold_in(jax.random.PRNGKey(req.seed), position)
        else:
            self.rng, key = jax.random.split(self.rng)
        return int(jax.random.categorical(key, logits_row / t))

    def _match_prefix(self, req: Request) -> list:
        """Trie lookup (pages retained for the caller) + counters."""
        if self.prefix is None:
            return []
        matched = self.prefix.match(req.prompt, self.alloc)
        obs.incr("engine.prefix.lookups")
        if matched:
            obs.incr("engine.prefix.hits")
            obs.incr("engine.prefix.tokens_saved",
                     len(matched) * self.page_size)
        return matched

    def _admit(self) -> int:
        """Move pending requests into free slots; returns how many joined."""
        admitted = 0
        while self.pending:
            free = [s for s in range(self.batch_slots) if s not in self.slots]
            if not free:
                break
            req = self.pending[0]
            plen = len(req.prompt)
            n = kvc.num_pages_needed(plen, self.page_size)
            matched = self._match_prefix(req)       # retained for this slot
            n_new = n - len(matched)
            if not self.alloc.can_alloc(n_new):
                if self.prefix is not None:
                    self.prefix.evict(self.alloc,
                                      n_new - self.alloc.free_pages)
                if not self.alloc.can_alloc(n_new):
                    if matched:
                        self.alloc.free(matched)    # drop this admission's
                    break                           # refs; wait for retire
            self.pending.popleft()
            self._request_phase(req.uid, "queue")
            slot = free[0]
            pages = matched + self.alloc.alloc(n_new)
            matched_len = len(matched) * self.page_size
            if matched or self.chunk_tokens is not None:
                # suffix/chunked prefill through the compiled chunk fn:
                # only positions >= matched_len are computed. Without
                # chunking the whole suffix goes in one padded chunk now;
                # with chunking the slot joins mid-prefill and advances
                # one chunk per step.
                self.state = kvc.assign_slot(self.state, slot, pages,
                                             matched_len)
                rec = _Slot(req=req, n_pages=n, generated=[], next_token=-1,
                            pages=pages, prefill_cursor=matched_len)
                self.slots[slot] = rec
                if self.chunk_tokens is None:
                    self._advance_prefill(slot, rec)   # completes in one go
            else:
                # exact-length prefill (compiled per prompt length): padding
                # the tokens to a page multiple would contaminate recurrent-
                # layer (ssm/rglru) slot state with the pad positions; the
                # partial last page is zero-filled by write_prefill_pages.
                self.state = kvc.assign_slot(self.state, slot, pages, plen)
                toks = np.asarray(req.prompt, np.int32)[None, :]
                entry = self._prefill_bucket(plen)
                with obs.span("engine.prefill", rid=req.uid, prompt_len=plen):
                    self.cache, logits = entry["prefill"](
                        self.params, jnp.asarray(toks), self.cache,
                        self.state["page_table"][slot], slot, plen)
                if self._spec:
                    dentry = self._prefill_bucket(plen, draft=True)
                    self.draft_cache, _ = dentry["prefill"](
                        self.draft_params, jnp.asarray(toks),
                        self.draft_cache, self.state["page_table"][slot],
                        slot, plen)
                with obs.span("engine.sample", rid=req.uid):
                    first = self._sample_slot(logits[0], req, plen)
                self._request_phase(req.uid, "prefill")
                self.slots[slot] = _Slot(req=req, n_pages=n,
                                         generated=[first], next_token=first,
                                         pages=pages)
                # the admission's first token is sampled off the prefill
                # logits, not a decode step — count it here so
                # tokens_generated covers every emitted token
                self.tokens_generated += 1
                obs.incr("engine.tokens_generated")
                if self.prefix is not None:
                    self.prefix.insert(req.prompt, pages, self.alloc)
            admitted += 1
            self.admissions += 1
            obs.incr("engine.admissions")
            self._note_occupancy()
        return admitted

    def _advance_prefill(self, slot: int, rec: _Slot) -> None:
        """Run ONE prefill chunk for a mid-prefill slot (the whole padded
        suffix at once when interleaved chunking is off). On the final
        chunk: sample the first token, mark the slot decode-ready, and
        register the prompt's full pages in the prefix trie."""
        req = rec.req
        plen = len(req.prompt)
        start = rec.prefill_cursor
        if self.chunk_tokens is not None:
            c = self.chunk_tokens
        else:
            c = _pow2(kvc.num_pages_needed(plen - start,
                                           self.page_size)) * self.page_size
        end = min(plen, start + c)
        toks = np.zeros((1, c), np.int32)
        toks[0, : end - start] = np.asarray(req.prompt[start:end], np.int32)
        last = (plen - 1 - start) if end == plen else 0
        entry = self._chunk_bucket(c)
        with obs.span("engine.prefill_chunk", rid=req.uid, start=start,
                      chunk=c):
            self.cache, logits = entry["chunk"](
                self.params, jnp.asarray(toks), self.cache,
                self.state["page_table"][slot],
                jnp.int32(start), jnp.int32(last))
        if self._spec:
            dentry = self._chunk_bucket(c, draft=True)
            self.draft_cache, _ = dentry["chunk"](
                self.draft_params, jnp.asarray(toks), self.draft_cache,
                self.state["page_table"][slot],
                jnp.int32(start), jnp.int32(last))
        self.chunks_prefilled += 1
        obs.incr("engine.chunks_prefilled")
        self.state["lengths"] = self.state["lengths"].at[slot].set(
            min(end, plen))
        if end >= plen:
            rec.prefill_cursor = -1
            with obs.span("engine.sample", rid=req.uid):
                first = self._sample_slot(logits[0], req, plen)
            self._request_phase(req.uid, "prefill")
            rec.generated = [first]
            rec.next_token = first
            self.tokens_generated += 1
            obs.incr("engine.tokens_generated")
            if self.prefix is not None:
                self.prefix.insert(req.prompt, rec.pages, self.alloc)
        else:
            rec.prefill_cursor = end

    def _try_grow(self, tokens_ahead: int = 1) -> list:
        """Allocate next pages for slots crossing a page boundary; returns
        the slots whose growth the exhausted pool could not cover.
        ``tokens_ahead`` > 1 (speculative rounds) reserves headroom for the
        whole verify block. Mid-prefill slots already hold every page their
        prompt needs, so they never grow (and never stall)."""
        stalled = []
        lengths = np.asarray(self.state["lengths"])   # one host transfer
        for slot in sorted(self.slots):
            rec = self.slots[slot]
            if rec.prefilling:
                continue
            need = int(lengths[slot]) + tokens_ahead
            while need > rec.n_pages * self.page_size:
                if not self.alloc.can_alloc(1) and self.prefix is not None:
                    # cached-but-unreferenced prefix pages are reclaimable
                    self.prefix.evict(self.alloc, 1)
                if self.alloc.can_alloc(1):
                    page = self.alloc.alloc(1)[0]
                    self.state["page_table"] = \
                        self.state["page_table"].at[slot, rec.n_pages].set(page)
                    rec.pages.append(page)
                    rec.n_pages += 1
                else:
                    stalled.append(slot)
                    break
        return stalled

    def _preempt(self, slot: int) -> None:
        """Recompute preemption (the vLLM policy): free the slot's pages and
        requeue a continuation — prompt := prompt + generated-so-far, budget
        := the remaining tokens — at the front of the queue. Re-admission
        re-prefills the lost KV; greedy decoding makes the continuation
        exact. Retirement later rebuilds the full result from the
        continuation's (longer) prompt, so the output is unchanged.

        Frees drop one reference per page: pages shared with the prefix
        trie (or another sequence) survive with their remaining refs, so a
        preemption never invalidates a neighbour's prefix."""
        rec = self.slots[slot]
        self.alloc.free(rec.pages)
        self.state = kvc.release_slot(self.state, slot)
        gen = rec.generated[: rec.req.max_new_tokens]
        cont = Request(
            rec.req.uid,
            np.concatenate([np.asarray(rec.req.prompt, np.int32),
                            np.asarray(gen, np.int32)]),
            max(0, rec.req.max_new_tokens - len(gen)),
            temperature=rec.req.temperature,
            seed=rec.req.seed)
        self.pending.appendleft(cont)
        self._request_phase(rec.req.uid, "decode", preempted=True)
        self.preemptions += 1
        obs.incr("engine.preemptions")
        del self.slots[slot]

    def _retire(self, slot: int, rec: _Slot) -> None:
        self.alloc.free(rec.pages)      # per-page ref drop, not a hard free
        self.state = kvc.release_slot(self.state, slot)
        gen = rec.generated[: rec.req.max_new_tokens]   # spec overshoot
        self.results[rec.req.uid] = np.concatenate(
            [np.asarray(rec.req.prompt, np.int32),
             np.asarray(gen, np.int32)])
        self._request_phase(rec.req.uid, "decode", reopen=False)
        del self.slots[slot]

    def _retire_finished(self) -> None:
        """Retire every decode-ready slot that has all its tokens."""
        done = [s for s, r in self.slots.items()
                if not r.prefilling
                and len(r.generated) >= r.req.max_new_tokens]
        if done:
            with obs.span("engine.retire"):
                for slot in done:
                    self._retire(slot, self.slots[slot])

    def _launch_views(self, active: list, mp_bucket: int):
        """(page_table, lengths, act) for a decode/verify launch. Mid-prefill
        slots are masked out by zeroing their rows: masked rows write to the
        null page and attend to nothing, so a chunk-interleaved slot never
        perturbs the batch it shares a launch with. With no mid-prefill
        slots the views are passed through untouched (the bitwise-identical
        fast path)."""
        pt = self.state["page_table"][:, :mp_bucket]
        lens = self.state["lengths"]
        act = np.zeros((self.batch_slots,), np.int32)
        for s in active:
            act[s] = 1
        act = jnp.asarray(act)
        if any(r.prefilling for r in self.slots.values()):
            pt = pt * act[:, None]
            lens = lens * act
        return pt, lens, act

    def _decode_one(self, active: list, mp_bucket: int) -> None:
        """One single-token decode step for every decode-ready slot."""
        entry = self._decode_bucket(mp_bucket)
        self._count_decode_step(active, mp_bucket,
                                entry["policies"]["attention_decode"])
        n_active = len(active)
        with obs.span("engine.decode_launch", active_slots=n_active,
                      mp_bucket=mp_bucket):
            pt, lens, act = self._launch_views(active, mp_bucket)
            tokens = np.zeros((self.batch_slots, 1), np.int32)
            for slot in active:
                tokens[slot, 0] = self.slots[slot].next_token
            self.cache, logits = entry["decode"](
                self.params, jnp.asarray(tokens), self.cache, pt, lens)
            self.state["lengths"] = self.state["lengths"] + act
        with obs.span("engine.sample"):
            sampled = {}
            greedy = None
            for slot in active:
                rec = self.slots[slot]
                pos = len(rec.req.prompt) + len(rec.generated)
                if self._effective_temperature(rec.req) == 0.0:
                    if greedy is None:      # one batched argmax for all
                        greedy = np.asarray(jnp.argmax(logits, axis=-1))
                    sampled[slot] = int(greedy[slot])
                    if self.logits_hook is not None:
                        self.logits_hook(rec.req.uid, pos, logits[slot])
                else:
                    sampled[slot] = self._sample_slot(logits[slot], rec.req,
                                                      pos)
        self.tokens_generated += n_active
        obs.incr("engine.tokens_generated", n_active)
        for slot in active:
            rec = self.slots[slot]
            rec.generated.append(sampled[slot])
            rec.next_token = sampled[slot]

    def _spec_round(self, active: list, mp_bucket: int) -> None:
        """One speculative round: k draft micro-steps propose d1..d_{k-1},
        the target verifies [t0, d1..d_{k-1}] in a single k-token decode,
        and each sequence keeps the longest agreeing prefix plus the
        target's first divergent token (1..k tokens per round).

        The draft runs k appends (the last feeds d_{k-1} with its logits
        discarded) so the draft cache has no hole at the round's final
        position. Rejected positions leave stale KV above the accepted
        length in both pools; the next round's appends start at the new
        length and cover every stale position before anything reads it."""
        k = self.spec_tokens
        dentry = self._decode_bucket(mp_bucket, draft=True)
        ventry = self._verify_bucket(mp_bucket)
        self._count_decode_step(active, mp_bucket,
                                ventry["policies"]["attention_decode"])
        pt, lens, act = self._launch_views(active, mp_bucket)
        base = np.asarray(self.state["lengths"])

        proposals = {s: [] for s in active}
        cur = np.zeros((self.batch_slots, 1), np.int32)
        for s in active:
            cur[s, 0] = self.slots[s].next_token
        with obs.span("engine.spec_draft", active_slots=len(active),
                      k=k, mp_bucket=mp_bucket):
            for i in range(k):
                self.draft_cache, dlogits = dentry["decode"](
                    self.draft_params, jnp.asarray(cur), self.draft_cache,
                    pt, lens + i * act if i else lens)
                if i == k - 1:
                    break               # KV-only append for d_{k-1}
                greedy = np.asarray(jnp.argmax(dlogits, axis=-1))
                for s in active:
                    proposals[s].append(int(greedy[s]))
                    cur[s, 0] = int(greedy[s])

        vt = np.zeros((self.batch_slots, k), np.int32)
        for s in active:
            vt[s, 0] = self.slots[s].next_token
            vt[s, 1:] = proposals[s]
        with obs.span("engine.spec_verify", active_slots=len(active),
                      k=k, mp_bucket=mp_bucket):
            self.cache, vlogits = ventry["verify"](
                self.params, jnp.asarray(vt), self.cache, pt, lens)
        preds = np.asarray(jnp.argmax(vlogits, axis=-1))    # (B, k)

        new_lengths = base.copy()
        for s in active:
            rec = self.slots[s]
            ds, ps = proposals[s], preds[s]
            j = 0
            while j < k - 1 and ds[j] == int(ps[j]):
                j += 1
            emitted = ds[:j] + [int(ps[j])]
            rec.generated.extend(emitted)
            rec.next_token = emitted[-1]
            new_lengths[s] = int(base[s]) + j + 1
            self.spec_proposed += k - 1
            self.spec_accepted += j
            self.spec_emitted += len(emitted)
            self.spec_participations += 1
            self.tokens_generated += len(emitted)
            obs.incr("engine.tokens_generated", len(emitted))
        self.state["lengths"] = jnp.asarray(new_lengths, jnp.int32)
        self.spec_rounds += 1
        obs.incr("engine.spec.rounds")
        obs.incr("engine.spec.proposed", (k - 1) * len(active))
        obs.incr("engine.spec.accepted",
                 sum(int(new_lengths[s] - base[s]) - 1 for s in active))

    def step(self) -> bool:
        """Admit, advance mid-prefill slots by one chunk, decode one step
        (or one speculative round) for every decode-ready slot, retire
        finished. Returns False when there is nothing left to do."""
        with obs.span("engine.step"):
            with obs.span("engine.admit"):
                self._admit()
            # chunk-interleaved prefill: one fixed-size chunk per slot per
            # step bounds the decode stall at one chunk instead of one full
            # prompt
            for slot in sorted(self.slots):
                rec = self.slots[slot]
                if rec.prefilling:
                    self._advance_prefill(slot, rec)
            # slots that completed at admission (max_new_tokens == 1)
            self._retire_finished()
            if not self.slots:
                if self.pending:
                    with obs.span("engine.admit"):
                        self._admit()
                    if not self.slots:
                        raise RuntimeError(
                            "paged engine stalled: pending requests but no "
                            "admissible slot (page pool too small?)")
                    return True
                return False

            # page growth; on pool exhaustion preempt the youngest stalled
            # slot (freeing its pages) until the survivors fit. A lone slot
            # never stalls: submit() bounds any single sequence to the pool.
            ahead = self.spec_tokens if self._spec else 1
            with obs.span("engine.grow"):
                stalled = self._try_grow(ahead)
                while stalled:
                    self._preempt(stalled[-1])
                    stalled = self._try_grow(ahead)
            if not self.slots:
                return bool(self.pending)   # all preempted; re-admit next
            active = [s for s, r in sorted(self.slots.items())
                      if not r.prefilling]
            if not active:
                self.steps += 1
                return True             # all slots mid-prefill; decode next
            max_pages = max(self.slots[s].n_pages for s in active)
            mp_bucket = min(self.max_pages_per_seq, _pow2(max_pages))
            self._note_occupancy()
            if self._spec:
                self._spec_round(active, mp_bucket)
            else:
                self._decode_one(active, mp_bucket)
            self.steps += 1
            self._retire_finished()
            return bool(self.slots or self.pending)

    def _count_decode_step(self, active: list, mp_bucket: int,
                           policy) -> None:
        """Counters of one decode launch, from host-side state: the paged
        kernel's blocks of ``policy.block_kv`` tokens that the decoding
        slots fill (the blocks it walks; the rest it skips) and that its
        grid holds, the pool's pages and the KV tokens held (each decoding
        slot's with the token this launch writes)."""
        ppb = policy.block_kv // self.page_size
        walked = sum(-(-self.slots[s].n_pages // ppb) for s in active)
        in_grid = self.batch_slots * -(-mp_bucket // ppb)
        self.kv_blocks_walked += walked
        self.kv_blocks_in_grid += in_grid
        if not obs.enabled():
            return
        obs.incr("engine.kv.blocks_walked", walked)
        obs.incr("engine.kv.blocks_in_grid", in_grid)
        obs.incr("engine.decode_steps")
        obs.incr("engine.kv.pages_held",
                 self.n_pages - 1 - self.alloc.free_pages)
        obs.incr("engine.kv.tokens_held", sum(
            r.prefill_cursor if r.prefilling
            else len(r.req.prompt) + len(r.generated)
            for r in self.slots.values()))

    def report(self) -> dict:
        """Engine-level metrics (the run report, DESIGN.md §13): counts are
        cumulative since construction, mirrored into the telemetry counters
        whenever a capture is active."""
        out = {
            "steps": self.steps,
            "admissions": self.admissions,
            "preemptions": self.preemptions,
            "tokens_generated": self.tokens_generated,
            "peak_pages_in_use": self.peak_pages_in_use,
            "page_pool_size": self.n_pages - 1,
            "kv_blocks": {"walked": self.kv_blocks_walked,
                          "in_grid": self.kv_blocks_in_grid},
            "bucket_lru": dict(self.lru_stats),
            "completed": len(self.results),
        }
        if self.prefix is not None:
            p = self.prefix
            out["prefix_cache"] = {
                "lookups": p.lookups,
                "hits": p.hits,
                "hit_rate": p.hits / p.lookups if p.lookups else 0.0,
                "matched_tokens": p.matched_tokens,
                "pages_held": p.pages_held,
            }
        if self.chunk_tokens is not None:
            out["chunked_prefill"] = {"chunk_tokens": self.chunk_tokens,
                                      "chunks": self.chunks_prefilled}
        if self._spec:
            out["speculative"] = {
                "k": self.spec_tokens,
                "rounds": self.spec_rounds,
                "proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "accept_rate": (self.spec_accepted / self.spec_proposed
                                if self.spec_proposed else 0.0),
                # emitted tokens per sequence per verify round, in [1, k]
                "mean_tokens_per_round":
                    (self.spec_emitted / self.spec_participations
                     if self.spec_participations else 0.0),
            }
        return out

    def run(self) -> dict:
        """Drive :meth:`step` until idle; returns {uid: tokens} results.
        :meth:`report` carries the run's engine metrics."""
        while self.step():
            pass
        return self.results