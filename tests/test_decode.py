"""Decode subsystem: split-KV flash-decode kernel, paged KV cache, engines.

Coverage per the acceptance bar (DESIGN.md §8):
  * kernel vs einsum reference across MHA / GQA / sliding-window /
    ring-buffer wrap-around, per-dtype tolerances, split-count invariance;
  * paged cache: page-boundary-crossing appends, prefill page writes,
    allocator lifecycle, paged kernel vs gathered reference;
  * model-level paged-vs-dense decode parity (reference numerics are
    bitwise identical by construction);
  * continuous batching end-to-end: mixed-length prompts joining and
    leaving mid-generation, greedy continuity vs the fixed-batch engine,
    per-bucket policy pinning, LRU bucket caps.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import autotune
from repro.core.policy import make_policy
from repro.kernels.attention import (attention_decode, attention_decode_paged,
                                     decode_ref, resolve_decode_policy,
                                     ring_positions)
from repro.models import build_model
from repro.serve import Engine, PagedEngine, Request, kv_cache as kvc

_TOL = {jnp.float32: 5e-6, jnp.bfloat16: 2e-2}


def _qkv(rng, b, h, hkv, s, d, dtype=jnp.float32):
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    return q, k, v


def _check(q, k, v, lengths, *, window=None, atol=None):
    atol = atol if atol is not None else _TOL[q.dtype.type]
    ref = attention_decode(q, k, v, lengths, window=window, mode="reference")
    ker = attention_decode(q, k, v, lengths, window=window,
                           mode="pallas_interpret")
    np.testing.assert_allclose(np.asarray(ker, np.float32),
                               np.asarray(ref, np.float32), atol=atol)
    return ref


class TestDecodeKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_mha_matches_reference(self, dtype):
        rng = np.random.default_rng(0)
        q, k, v = _qkv(rng, 2, 4, 4, 64, 32, dtype)
        _check(q, k, v, jnp.array([17, 64], jnp.int32))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gqa_matches_reference(self, dtype):
        rng = np.random.default_rng(1)
        q, k, v = _qkv(rng, 2, 8, 2, 64, 32, dtype)
        _check(q, k, v, jnp.array([5, 48], jnp.int32))

    def test_sliding_window(self):
        rng = np.random.default_rng(2)
        q, k, v = _qkv(rng, 2, 4, 2, 64, 16)
        _check(q, k, v, jnp.array([30, 64], jnp.int32), window=8)

    def test_ring_buffer_wraparound(self):
        """lengths > slots: the cache holds the last ``slots`` positions."""
        rng = np.random.default_rng(3)
        q, k, v = _qkv(rng, 2, 4, 2, 32, 16)
        out = _check(q, k, v, jnp.array([100, 33], jnp.int32))
        # wrapped rows attend to every slot: all slots valid
        _, valid = ring_positions(jnp.array([100, 33], jnp.int32), 32)
        assert bool(valid.all())

    def test_ring_window_composition(self):
        rng = np.random.default_rng(4)
        q, k, v = _qkv(rng, 1, 2, 2, 32, 16)
        _check(q, k, v, jnp.array([77], jnp.int32), window=12)

    def test_empty_sequence_returns_zeros(self):
        rng = np.random.default_rng(5)
        q, k, v = _qkv(rng, 2, 4, 2, 32, 16)
        out = attention_decode(q, k, v, jnp.array([0, 9], jnp.int32),
                               mode="pallas_interpret")
        assert float(jnp.max(jnp.abs(out[0]))) == 0.0
        assert float(jnp.max(jnp.abs(out[1]))) > 0.0

    def test_split_count_invariance(self):
        """The LSE combine is exact: any split size gives the same output."""
        rng = np.random.default_rng(6)
        q, k, v = _qkv(rng, 1, 4, 2, 64, 16)
        lengths = jnp.array([50], jnp.int32)
        outs = []
        for bkv in (16, 32, 64):
            pol = make_policy("attention_decode", block_m=2, block_n=bkv,
                              block_k=16, in_dtype="float32")
            outs.append(np.asarray(attention_decode(
                q, k, v, lengths, policy=pol, mode="pallas_interpret")))
        np.testing.assert_allclose(outs[0], outs[1], atol=2e-6)
        np.testing.assert_allclose(outs[0], outs[2], atol=2e-6)

    def test_scalar_length_broadcasts(self):
        rng = np.random.default_rng(7)
        q, k, v = _qkv(rng, 2, 4, 2, 32, 16)
        a = attention_decode(q, k, v, 20, mode="pallas_interpret")
        b = attention_decode(q, k, v, jnp.array([20, 20]),
                             mode="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestDecodePolicy:
    def test_autotuned_policy_is_legal_and_tiles(self):
        pol = autotune.select_policy("attention_decode", (4, 8, 4, 4096, 128))
        assert pol.is_legal()
        assert 4096 % pol.block_kv == 0

    def test_small_grid_prefers_splits(self):
        """With batch*kv_heads == 1 the bandwidth model must manufacture
        grid parallelism by splitting KV (the reason the kernel exists)."""
        pol = autotune.select_policy("attention_decode", (1, 1, 8, 8192, 128))
        assert 8192 // pol.block_kv > 1

    def test_paged_policy_fixes_split_to_page(self):
        """A paged split is a block of whole pages, about 512 tokens,
        within the table, and a function of the launch shape alone (the
        engine's pinned policy is the traced one); a tall multi-token q
        tile that would overflow VMEM halves it."""
        for page, kv_len, want in ((32, 256, 256), (64, 4096, 512),
                                   (16, 64, 64), (1024, 4096, 1024)):
            pol = resolve_decode_policy(2, 4, 2, kv_len, 64, "bfloat16",
                                        page_size=page)
            assert pol.block_kv == want
            assert pol.block_kv % page == 0 and pol.block_kv <= kv_len
            assert pol == resolve_decode_policy(2, 4, 2, kv_len, 64,
                                                "bfloat16", page_size=page)
        chunk = resolve_decode_policy(1, 8, 4, 4096, 128, "bfloat16",
                                      page_size=64, q_tokens=512)
        tall = resolve_decode_policy(1, 8, 4, 4096, 128, "bfloat16",
                                     page_size=64, q_tokens=4096)
        assert (chunk.block_kv, tall.block_kv) == (512, 256)

    def test_policies_for_model_includes_decode(self):
        cfg = get_config("granite-8b", smoke=True)
        pols = autotune.policies_for_model(cfg, batch=2, seq_len=128,
                                           decode_len=256)
        assert "attention_decode" in pols
        assert pols["attention_decode"].op == "attention_decode"


class TestPagedCache:
    def _pool(self, rng, P=8, hkv=2, page=8, d=16):
        pool = kvc.init_page_pool(P, hkv, page, d, jnp.float32)
        return pool["k_pages"], pool["v_pages"]

    def test_append_crosses_page_boundary(self):
        rng = np.random.default_rng(0)
        k_pages, v_pages = self._pool(rng)
        page = 8
        pt = jnp.array([[3, 5, 0, 0]], jnp.int32)
        toks = [np.asarray(rng.normal(size=(1, 2, 1, 16)), np.float32)
                for _ in range(12)]       # 12 tokens > one 8-slot page
        for i, t in enumerate(toks):
            k_pages, v_pages = kvc.append_paged_kv(
                k_pages, v_pages, jnp.asarray(t), jnp.asarray(t), pt,
                jnp.array([i], jnp.int32))
        got = np.asarray(kvc.gather_pages(k_pages, pt))   # (1, 2, 32, 16)
        want = np.concatenate(toks, axis=2)               # (1, 2, 12, 16)
        np.testing.assert_array_equal(got[:, :, :12], want)

    def test_prefill_write_then_append_matches_dense(self):
        rng = np.random.default_rng(1)
        k_pages, v_pages = self._pool(rng)
        page, s_true = 8, 11
        k = jnp.asarray(rng.normal(size=(1, 2, s_true, 16)), jnp.float32)
        rows = jnp.array([2, 6, 0, 0], jnp.int32)
        k_pages, v_pages = kvc.write_prefill_pages(k_pages, v_pages, k, k,
                                                   rows)
        # append 3 more tokens, starting mid-page-2 and crossing into page 3
        pt = jnp.array([[2, 6, 7, 0]], jnp.int32)
        extra = [np.asarray(rng.normal(size=(1, 2, 1, 16)), np.float32)
                 for _ in range(6)]
        kp2, vp2 = k_pages, v_pages
        for i, t in enumerate(extra):
            kp2, vp2 = kvc.append_paged_kv(kp2, vp2, jnp.asarray(t),
                                           jnp.asarray(t), pt,
                                           jnp.array([s_true + i], jnp.int32))
        got = np.asarray(kvc.gather_pages(kp2, pt))
        want = np.concatenate([np.asarray(k)] + extra, axis=2)
        np.testing.assert_array_equal(got[:, :, : s_true + 6], want)

    # (page, mp, heads, kv heads, lengths, extras): the default policy walks
    # 512 tokens of pages a grid step, so page 128 gives 4-page blocks and
    # page 256 2-page blocks. Lengths sit at 0, 1, page - 1, a block edge
    # +-1 and a full table; a slot's table entries past its pages are the
    # null page 0.
    _EDGES = [0, 1, 127, 511, 512, 513, 768]
    _PAGED_CASES = {
        "small-window-none": (16, 4, 4, 2, [55, 20], {}),
        "small-window-8": (16, 4, 4, 2, [55, 20], {"window": 8}),
        "edges-gqa4": (128, 6, 8, 2, _EDGES, {}),
        "edges-mha": (128, 6, 2, 2, _EDGES, {}),
        "edges-bf16": (128, 6, 8, 2, _EDGES, {"dtype": jnp.bfloat16}),
        "window-softcap": (128, 6, 4, 2, _EDGES,
                           {"window": 200, "softcap": 5.0}),
        "sinks": (256, 5, 8, 2, [0, 255, 256, 511, 513, 1280],
                  {"sinks": True}),
        "table-shorter-than-block": (128, 3, 4, 2, [0, 130, 384],
                                     {"block_pages": 4}),
        "verify-3-tokens": (128, 6, 4, 2, [3, 128, 512, 514, 768],
                            {"q_tokens": 3, "window": 300}),
    }

    @pytest.mark.parametrize("case", list(_PAGED_CASES))
    def test_paged_kernel_matches_reference(self, case):
        """The block-walking paged kernel against the gathered einsum
        oracle across block edges, short tables and every epilogue."""
        page, mp, h, hkv, lengths, ex = self._PAGED_CASES[case]
        dtype = ex.get("dtype", jnp.float32)
        t, b, d = ex.get("q_tokens", 1), len(lengths), 32
        rng = np.random.default_rng(2)
        held = [-(-n // page) for n in lengths]
        P = 1 + sum(held)
        kp = jnp.asarray(rng.normal(size=(P, hkv, page, d)), dtype)
        vp = jnp.asarray(rng.normal(size=(P, hkv, page, d)), dtype)
        q = jnp.asarray(rng.normal(size=(b, h, t, d)), dtype)
        ids = rng.permutation(np.arange(1, P))
        pt = np.zeros((b, mp), np.int32)
        for i, n in enumerate(held):
            pt[i, :n], ids = ids[:n], ids[n:]
        kw = {"window": ex.get("window"), "softcap": ex.get("softcap")}
        if ex.get("sinks"):
            kw["sinks"] = jnp.asarray(rng.normal(size=(h,)), jnp.float32)
        policy = None
        if "block_pages" in ex:     # a block longer than the whole table
            policy = resolve_decode_policy(
                b, hkv, h // hkv, ex["block_pages"] * page, d, dtype,
                page_size=page)
            assert policy.block_kv == ex["block_pages"] * page > mp * page
        args = (q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths, jnp.int32))
        ref = attention_decode_paged(*args, mode="reference", **kw)
        ker = attention_decode_paged(*args, mode="pallas_interpret",
                                     policy=policy, **kw)
        atol = 5e-6 if dtype == jnp.float32 else _TOL[dtype]
        np.testing.assert_allclose(np.asarray(ker, np.float32),
                                   np.asarray(ref, np.float32), atol=atol)

    def test_allocator_lifecycle(self):
        alloc = kvc.PageAllocator(5)       # pages 1..4 usable
        a = alloc.alloc(2)
        b = alloc.alloc(2)
        assert set(a) | set(b) == {1, 2, 3, 4}
        assert not alloc.can_alloc(1)
        with pytest.raises(MemoryError):
            alloc.alloc(1)
        alloc.free(a)
        assert alloc.can_alloc(2)
        with pytest.raises(ValueError):
            alloc.free(a)                  # double free
        with pytest.raises(ValueError):
            alloc.free([0])                # null page is not freeable


class TestPagedModelParity:
    def test_paged_decode_matches_dense(self):
        """Dense-bucket and paged decode paths agree bitwise in reference
        mode, including across a page-boundary-crossing append."""
        cfg = get_config("granite-8b", smoke=True)
        model = build_model(cfg, mode="reference")
        params = model.init(jax.random.PRNGKey(0))
        prompt = np.array([[5, 6, 7, 8, 9, 10]], np.int32)
        page, mp, n_pages = 4, 4, 12     # prompt needs 2 pages; crossing soon

        dc, dlog = model.prefill(params, jnp.asarray(prompt),
                                 model.init_cache(1, 32))
        cache = model.init_paged_cache(2, n_pages, page)
        alloc = kvc.PageAllocator(n_pages)
        state = kvc.init_page_state(2, mp)
        pages = alloc.alloc(2)
        state = kvc.assign_slot(state, 0, pages, 6)
        n_alloc = 2
        toks = np.zeros((1, 8), np.int32)
        toks[0, :6] = prompt[0]
        cache, plog = model.prefill_paged(params, jnp.asarray(toks), cache,
                                          state["page_table"][0], 0, 6)
        np.testing.assert_array_equal(np.asarray(dlog), np.asarray(plog))

        tok = jnp.argmax(dlog, -1)[:, None]
        for i in range(4):
            if int(state["lengths"][0]) + 1 > n_alloc * page:
                new = alloc.alloc(1)[0]
                state["page_table"] = \
                    state["page_table"].at[0, n_alloc].set(new)
                n_alloc += 1
            dc, dlog = model.decode_step(params, tok, dc, 6 + i)
            tok2 = jnp.concatenate([tok, jnp.zeros((1, 1), jnp.int32)], 0)
            cache, plog = model.decode_step_paged(
                params, tok2, cache, state["page_table"], state["lengths"])
            state["lengths"] = state["lengths"].at[0].add(1)
            np.testing.assert_array_equal(np.asarray(dlog[0]),
                                          np.asarray(plog[0]))
            tok = jnp.argmax(dlog, -1)[:, None]


class TestPagedEngine:
    def _model(self):
        cfg = get_config("granite-8b", smoke=True)
        model = build_model(cfg, mode="reference")
        return model, model.init(jax.random.PRNGKey(0)), cfg

    def test_continuous_batching_matches_fixed_engine(self):
        """Mixed-length prompts join and leave mid-generation; every
        result must equal the fixed-batch engine's greedy decode."""
        model, params, cfg = self._model()
        eng = PagedEngine(model, params, batch_slots=2, page_size=8,
                          max_pages_per_seq=4)
        rng = np.random.default_rng(0)
        reqs = []
        for uid in range(4):
            plen = int(rng.integers(3, 14))
            reqs.append(Request(uid, rng.integers(
                0, cfg.vocab_size, plen).astype(np.int32),
                int(rng.integers(2, 7))))
            eng.submit(reqs[-1])
        results = eng.run()
        assert sorted(results) == [0, 1, 2, 3]
        assert eng.alloc.free_pages == eng.n_pages - 1   # all pages freed
        fixed = Engine(model, params, max_len=64)
        for r in reqs:
            want = fixed.generate(r.prompt[None, :], r.max_new_tokens)
            np.testing.assert_array_equal(results[r.uid], want.tokens[0])

    def test_logits_hook_sees_every_emitted_token(self):
        """logits_hook gets, at each token's absolute position, the row the
        greedy token is the argmax of; speculative engines refuse it."""
        model, params, cfg = self._model()
        seen = {}
        eng = PagedEngine(model, params, batch_slots=2, page_size=8,
                          max_pages_per_seq=4,
                          logits_hook=lambda uid, pos, row: seen.__setitem__(
                              (uid, pos), np.asarray(row)))
        reqs = [Request(0, np.arange(1, 6, dtype=np.int32), 4),
                Request(1, np.arange(2, 12, dtype=np.int32), 3)]
        for r in reqs:
            eng.submit(r)
        results = eng.run()
        assert len(seen) == sum(r.max_new_tokens for r in reqs)
        for r in reqs:
            for pos in range(len(r.prompt), len(results[r.uid])):
                assert int(np.argmax(seen[(r.uid, pos)])) == \
                    results[r.uid][pos]
        with pytest.raises(ValueError, match="logits_hook"):
            PagedEngine(model, params, draft_model=model,
                        draft_params=params, spec_tokens=3,
                        logits_hook=lambda *a: None)

    def test_decode_policies_pinned_per_bucket(self):
        model, params, cfg = self._model()
        eng = PagedEngine(model, params, batch_slots=2, page_size=8,
                          max_pages_per_seq=4)
        eng.submit(Request(0, np.arange(3, dtype=np.int32), 3))
        eng.run()
        decode_keys = [k for k in eng.bucket_policies
                       if isinstance(k[0], int)]
        assert decode_keys, eng.bucket_policies
        for k in decode_keys:
            pol = eng.bucket_policies[k]["attention_decode"]
            # a split is a block of whole pages within the bucket
            assert pol.block_kv % 8 == 0 and pol.block_kv <= 8 * k[1]

    @pytest.mark.parametrize("arch", ["mamba2-130m", "recurrentgemma-2b"])
    def test_recurrent_arch_parity(self, arch):
        """Regression: prompts whose length is NOT a page multiple must not
        contaminate recurrent (ssm/rglru) slot state — the engine prefills
        at exact length, so every generated token matches the dense path."""
        cfg = get_config(arch, smoke=True)
        model = build_model(cfg, mode="reference")
        params = model.init(jax.random.PRNGKey(0))
        eng = PagedEngine(model, params, batch_slots=2, page_size=8,
                          max_pages_per_seq=4)
        prompt = np.arange(1, 6, dtype=np.int32)     # len 5: partial page
        eng.submit(Request(0, prompt, 6))
        results = eng.run()
        fixed = Engine(model, params, max_len=32)
        want = fixed.generate(prompt[None, :], 6).tokens[0]
        np.testing.assert_array_equal(results[0], want)

    def test_pool_exhaustion_preempts_and_completes(self):
        """Regression: just-in-time page growth over an exhausted pool must
        preempt (recompute policy), not crash — and the preempted request
        still finishes with exactly the fixed-batch engine's output."""
        model, params, cfg = self._model()
        eng = PagedEngine(model, params, batch_slots=2, page_size=4,
                          max_pages_per_seq=4, n_pages=5)   # 4-page pool
        rng = np.random.default_rng(3)
        reqs = [Request(u, rng.integers(0, cfg.vocab_size, 4)
                        .astype(np.int32), 12) for u in range(2)]
        for r in reqs:
            eng.submit(r)
        results = eng.run()
        assert eng.preemptions > 0
        assert eng.alloc.free_pages == eng.n_pages - 1
        # the run report carries the same story: forced preemption, a pool
        # that actually filled, and the bucket-LRU stats block
        rep = eng.report()
        assert rep["preemptions"] == eng.preemptions > 0
        assert rep["admissions"] >= len(reqs)   # re-admits count too
        assert 0 < rep["peak_pages_in_use"] <= rep["page_pool_size"] == 4
        assert rep["tokens_generated"] >= sum(r.max_new_tokens
                                              for r in reqs)
        assert set(rep["bucket_lru"]) == {"hits", "misses", "evictions"}
        assert rep["completed"] == len(reqs)
        fixed = Engine(model, params, max_len=64)
        for r in reqs:
            want = fixed.generate(r.prompt[None, :], r.max_new_tokens)
            np.testing.assert_array_equal(results[r.uid], want.tokens[0])

    def test_kv_block_counters(self):
        """The paged kernel's blocks walked and held in its grid, counted
        from host state: 256-token pages make 2-page blocks, so a 3-page
        slot walks 2 blocks and a 1-page slot 1, of the 2 x 2 blocks the
        4-page bucket's grid holds."""
        from repro import obs
        model, params, cfg = self._model()
        eng = PagedEngine(model, params, batch_slots=2, page_size=256,
                          max_pages_per_seq=4)
        rng = np.random.default_rng(4)
        with obs.capture() as cap:
            for uid, plen in enumerate((600, 5)):
                eng.submit(Request(uid, rng.integers(
                    0, cfg.vocab_size, plen).astype(np.int32), 4))
            eng.run()
        steps = cap.counter("engine.decode_steps")
        assert steps == 3       # the first token comes from the prefill
        assert cap.counter("engine.kv.blocks_walked") == 3 * steps
        assert cap.counter("engine.kv.blocks_in_grid") == 4 * steps
        assert eng.report()["kv_blocks"] == {"walked": 3 * steps,
                                             "in_grid": 4 * steps}

    def test_rejects_oversized_request(self):
        model, params, cfg = self._model()
        eng = PagedEngine(model, params, batch_slots=2, page_size=4,
                          max_pages_per_seq=2)
        with pytest.raises(ValueError):
            eng.submit(Request(0, np.arange(7, dtype=np.int32), 5))

    def test_engine_bucket_lru_cap(self):
        model, params, cfg = self._model()
        eng = Engine(model, params, max_len=32, max_cached_buckets=2)
        for s in (4, 8, 12):
            eng.generate(np.ones((1, s), np.int32), 2)
        assert len(eng.bucket_policies) == 2
        assert (1, 4) not in eng.bucket_policies   # LRU evicted


class TestKernelModeEndToEnd:
    def test_paged_engine_kernel_mode_matches_reference(self):
        """The full serve loop over the Pallas (interpret) decode kernel
        produces the same greedy tokens as the einsum reference path.
        float32 compute: greedy equality is only well posed where the two
        paths agree far below the top-two logit margins; bf16 rounding
        alone flips near ties."""
        cfg = dataclasses.replace(get_config("granite-8b", smoke=True),
                                  compute_dtype="float32")
        params = build_model(cfg, mode="reference").init(jax.random.PRNGKey(0))
        outs = {}
        for mode in ("reference", "pallas_interpret"):
            model = build_model(cfg, mode=mode)
            eng = PagedEngine(model, params, batch_slots=2, page_size=8,
                              max_pages_per_seq=2)
            eng.submit(Request(0, np.arange(1, 6, dtype=np.int32), 4))
            eng.submit(Request(1, np.arange(2, 12, dtype=np.int32), 3))
            outs[mode] = eng.run()
        for uid in (0, 1):
            np.testing.assert_array_equal(outs["reference"][uid],
                                          outs["pallas_interpret"][uid])
