"""Telemetry subsystem (DESIGN.md §13): launch journal, spans/counters,
plan audit, exporters — and the zero-overhead contract on the disabled
path.

Coverage per the acceptance bar:
  * disabled path is a no-op: instrumented kernels run with no capture
    active and ``obs.null_allocations()`` stays 0 (the tripwire that
    every recording helper returned before allocating);
  * the journal reproduces DESIGN.md §12 launch counts (3 fwd / 5 bwd for
    the decoder attention sublayer) — asserted in test_attention_fusion;
    here the journal is checked at the single-kernel level (op names,
    policy payloads, modeled dma_bytes);
  * spans nest (parent, inherited request id), intervals keep their given
    bounds, and ``capture(annotate=True)`` puts spans into a profiler
    trace;
  * the plan-audit journal records every select_policy/select_fusion
    verdict with losing candidates, and replays memo hits (cached=True);
  * exporters: Chrome-trace JSON parses and passes tools/trace_check.py;
    counters JSON keys are stable;
  * engine/trainer counters surface through capture (admissions,
    preemptions, bucket-LRU, trainer steps), with the engine's step
    children and request phases, the trainer's step children, and the
    names of the engine's compiled programs.
"""
import dataclasses
import glob
import json
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import autotune
from repro.kernels.gemm import Epilogue, Prologue, gemm, gemm_fused

REPO = pathlib.Path(__file__).resolve().parent.parent


def _rand(key, shape, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32) * 0.5
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Disabled path: the zero-overhead contract
# ---------------------------------------------------------------------------

class TestDisabledPath:
    def test_recording_api_is_noop_without_capture(self):
        assert not obs.enabled()
        obs.incr("nope")
        obs.gauge("nope", 3.0)
        obs.plan_decision("policy", "gemm", (1, 1, 1), "f32", {})
        with obs.span("nope", k=1):
            pass
        obs.interval("nope", 0.0, 1.0, rid=3)
        assert not obs.enabled()

    def test_instrumented_kernels_allocate_nothing_when_disabled(self):
        """The acceptance criterion: a full instrumented dispatch with no
        recorder active must build zero event objects."""
        obs.reset_null_allocations()
        a, b = _rand(0, (64, 64)), _rand(1, (64, 64))
        jax.block_until_ready(gemm(a, b, out_dtype=jnp.float32))
        jax.block_until_ready(gemm_fused(
            a, b, b2=_rand(2, (64, 64)),
            epilogue=Epilogue(activation="silu", gate=True),
            out_dtype=jnp.float32))
        assert obs.null_allocations() == 0

    def test_tripwire_fires_on_unguarded_record(self):
        obs.reset_null_allocations()
        obs._record(obs.LaunchEvent(op="rogue"))
        assert obs.null_allocations() == 1
        obs.reset_null_allocations()


# ---------------------------------------------------------------------------
# Launch journal
# ---------------------------------------------------------------------------

class TestLaunchJournal:
    def test_gemm_event_carries_policy_and_modeled_bytes(self):
        a, b = _rand(0, (128, 128)), _rand(1, (128, 128))
        with obs.capture() as cap:
            gemm(a, b, out_dtype=jnp.float32)
        assert cap.count("gemm") == 1
        ev = cap.launches[0]
        assert ev.grid and all(g >= 1 for g in ev.grid)
        assert ev.policy and "schedule" in ev.policy and "blocks" in ev.policy
        assert ev.dma_bytes and ev.dma_bytes > 0
        assert ev.flops == 2 * 128 * 128 * 128
        assert cap.modeled_bytes("gemm") == ev.dma_bytes

    def test_gemm_fused_event_carries_chain(self):
        a, b = _rand(0, (128, 128)), _rand(1, (128, 128))
        with obs.capture() as cap:
            gemm_fused(a, b, b2=_rand(2, (128, 128)),
                       epilogue=Epilogue(activation="silu", gate=True),
                       out_dtype=jnp.float32)
        ev = cap.launches[-1]
        assert ev.op == "gemm_fused"
        assert ev.chain and "silu" in ev.chain

    def test_fused_norm_and_rope_journal(self):
        from repro.kernels.fused_norm import fused_dropout_residual_layernorm
        from repro.kernels.rope import rope_pallas, rope_tables
        x = _rand(0, (64, 128))
        gamma = jnp.ones((128,))
        beta = jnp.zeros((128,))
        with obs.capture() as cap:
            fused_dropout_residual_layernorm(x, jnp.zeros_like(x), gamma,
                                             beta, 0)
            q = _rand(1, (1, 2, 64, 64))
            sin, cos = rope_tables(jnp.arange(64), 64)
            rope_pallas(q, sin, cos)
        assert cap.count("fused_norm") == 1, cap.launch_counts()
        assert cap.count("rope") == 1, cap.launch_counts()
        assert cap.modeled_bytes() > 0

    def test_nested_captures_fan_out(self):
        a, b = _rand(0, (64, 64)), _rand(1, (64, 64))
        with obs.capture() as outer:
            gemm(a, b, out_dtype=jnp.float32)
            with obs.capture() as inner:
                gemm(a, b, out_dtype=jnp.float32)
        assert inner.count("gemm") == 1
        assert outer.count("gemm") == 2


# ---------------------------------------------------------------------------
# Spans + counters
# ---------------------------------------------------------------------------

class TestSpansCounters:
    def test_span_counter_gauge_basics(self):
        with obs.capture() as cap:
            with obs.span("outer", tag="x"):
                obs.incr("hits")
                obs.incr("hits", 2.0)
                obs.gauge("peak", 3.0)
                obs.gauge("peak", 1.0)   # running max keeps 3
        assert cap.counter("hits") == 3.0
        assert cap.counter("peak") == 3.0
        assert [s.name for s in cap.spans] == ["outer"]
        assert cap.spans[0].meta == {"tag": "x"}
        assert cap.spans[0].dur >= 0

    def test_spans_know_parent_and_inherit_rid(self):
        with obs.capture() as cap:
            with obs.span("step"):
                with obs.span("chunk", rid=7):
                    with obs.span("sample"):
                        pass
                with obs.span("grow"):
                    pass
        by = {s.name: s for s in cap.spans}
        assert by["step"].parent is None and by["step"].rid is None
        assert by["chunk"].parent is by["step"] and by["chunk"].rid == 7
        assert by["sample"].parent is by["chunk"] and by["sample"].rid == 7
        assert by["grow"].parent is by["step"] and by["grow"].rid is None
        # children lie inside their parent, on the perf_counter clock
        for child in ("chunk", "grow"):
            c, p = by[child], by["step"]
            assert p.ts <= c.ts and c.ts + c.dur <= p.ts + p.dur
        assert abs(by["step"].ts - time.perf_counter()) < 60

    def test_interval_keeps_given_bounds(self):
        t0 = time.perf_counter()
        with obs.capture() as cap:
            with obs.span("step"):
                obs.interval("request.queue", t0 - 2.0, t0 - 0.5, rid=4,
                             preempted=True)
        iv = next(s for s in cap.spans if s.name == "request.queue")
        assert (iv.ts, iv.dur, iv.rid) == (t0 - 2.0, 1.5, 4)
        assert iv.parent is None and iv.meta == {"preempted": True}

    def test_summary_block_shape(self):
        a, b = _rand(0, (64, 64)), _rand(1, (64, 64))
        with obs.capture() as cap:
            with obs.span("s"):
                gemm(a, b, out_dtype=jnp.float32)
            obs.incr("c")
        s = cap.summary()
        assert s["launches"] == {"gemm": 1}
        assert s["modeled_dma_bytes"]["gemm"] > 0
        # the interpreted kernel call is counted too (kernels/modes.py)
        assert s["counters"] == {"c": 1.0, "kernels.interpret_launch": 1.0}
        assert s["spans"] == 1


    def test_annotate_puts_spans_in_profiler_trace(self, tmp_path):
        """The profiler sink: each span is a TraceAnnotation named with the
        documented prefix and carrying its rid, on the profile's clock."""
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.capture(annotate=True) as cap:
                with obs.span("engine.step"):
                    with obs.span("engine.sample", rid=7):
                        jax.block_until_ready(jnp.ones(8) * 2)
            with obs.capture() as quiet:        # no sink: nothing traced
                with obs.span("engine.retire"):
                    pass
        finally:
            jax.profiler.stop_trace()
        assert len(cap.spans) == 2 and len(quiet.spans) == 1
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        found = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(obs.PROFILER_PREFIX):
                        found[ev.name] = (dict(ev.stats), ev.start_ns,
                                          ev.duration_ns)
        assert set(found) == {"repro.engine.step", "repro.engine.sample"}
        stats, s0, d0 = found["repro.engine.sample"]
        assert stats.get("rid") == 7
        _, s1, d1 = found["repro.engine.step"]
        assert s1 <= s0 and s0 + d0 <= s1 + d1


# ---------------------------------------------------------------------------
# Plan-audit journal
# ---------------------------------------------------------------------------

class TestPlanAudit:
    def test_select_policy_audited_with_candidates(self):
        autotune.clear_policy_cache()
        with obs.capture() as cap:
            autotune.select_policy("gemm", (512, 512, 512), "bfloat16")
        pols = [p for p in cap.plans if p.kind == "policy"]
        assert len(pols) == 1
        dec = pols[0]
        assert dec.op == "gemm" and not dec.cached
        assert dec.candidates and any(c["chosen"] for c in dec.candidates)
        assert all("dma_bytes" in c and "time_s" in c
                   for c in dec.candidates)

    def test_memo_hit_replays_audit_as_cached(self):
        autotune.clear_policy_cache()
        autotune.select_policy("gemm", (512, 512, 512), "bfloat16")  # warm
        with obs.capture() as cap:
            autotune.select_policy("gemm", (512, 512, 512), "bfloat16")
        pols = [p for p in cap.plans if p.kind == "policy"]
        assert len(pols) == 1 and pols[0].cached
        assert pols[0].chosen  # the stored describe() payload replays

    def test_select_fusion_audited(self):
        autotune.clear_policy_cache()
        with obs.capture() as cap:
            plan = autotune.select_fusion("mlp", (4096, 1024, 4096, True))
        fus = [p for p in cap.plans if p.kind == "fusion"]
        assert len(fus) == 1
        dec = fus[0]
        assert dec.chosen["plan"] == plan["plan"]
        assert {c["plan"] for c in dec.candidates} == {"fused", "unfused"}


# ---------------------------------------------------------------------------
# Exporters + tools/trace_check.py
# ---------------------------------------------------------------------------

class TestExporters:
    def _run_captured(self):
        a, b = _rand(0, (128, 128)), _rand(1, (128, 128))
        autotune.clear_policy_cache()
        with obs.capture() as cap:
            with obs.span("window", case="test"):
                gemm(a, b, out_dtype=jnp.float32)
                gemm_fused(a, b, out_dtype=jnp.float32)
            obs.incr("tokens", 7)
        return cap

    def test_chrome_trace_schema(self, tmp_path):
        cap = self._run_captured()
        path = obs.export_chrome_trace(cap, tmp_path / "TRACE_t.json")
        doc = json.loads(pathlib.Path(path).read_text())
        evs = doc["traceEvents"]
        assert evs and all(
            isinstance(e["name"], str) and isinstance(e["pid"], int)
            and isinstance(e["ts"], (int, float)) and e["ph"] in "XiC"
            for e in evs)
        assert any(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
        counter_evs = [e for e in evs if e["ph"] == "C"]
        assert any(e["name"] == "tokens" for e in counter_evs)
        # timed from the recorder's first event, whatever the clock reads
        assert min(e["ts"] for e in evs) == 0.0
        assert doc["otherData"]["producer"] == "repro.obs"
        assert isinstance(doc["otherData"]["plan_decisions"], list)

    def test_counters_export_stable_keys(self, tmp_path):
        cap = self._run_captured()
        path = obs.export_counters(cap, tmp_path / "COUNTERS_t.json")
        doc = json.loads(pathlib.Path(path).read_text())
        assert list(doc) == ["counters", "launches"]
        assert doc["counters"]["tokens"] == 7
        assert doc["launches"] == {"gemm": 1, "gemm_fused": 1}

    def test_trace_check_tool_passes_on_real_exports(self, tmp_path):
        cap = self._run_captured()
        obs.export_chrome_trace(cap, tmp_path / "TRACE_t.json")
        obs.export_counters(cap, tmp_path / "COUNTERS_t.json")
        res = subprocess.run(
            [sys.executable, str(REPO / "tools" / "trace_check.py"),
             str(tmp_path)], capture_output=True, text=True)
        assert res.returncode == 0, res.stderr

    def test_trace_check_tool_rejects_malformed(self, tmp_path):
        (tmp_path / "TRACE_bad.json").write_text(
            json.dumps({"traceEvents": [{"ph": "X"}]}))
        res = subprocess.run(
            [sys.executable, str(REPO / "tools" / "trace_check.py"),
             str(tmp_path)], capture_output=True, text=True)
        assert res.returncode == 1
        assert "TRACE_bad.json" in res.stderr

    def test_bench_json_embeds_telemetry(self, tmp_path, monkeypatch):
        """benchmarks.common bracket: begin/end_capture feeds a telemetry
        block + trace/counter exports into write_bench_json."""
        sys.path.insert(0, str(REPO))
        try:
            from benchmarks import common as bcommon
        finally:
            sys.path.pop(0)
        monkeypatch.setenv("BENCH_OUT", str(tmp_path))
        a, b = _rand(0, (64, 64)), _rand(1, (64, 64))
        bcommon.begin_capture()
        gemm(a, b, out_dtype=jnp.float32)
        bcommon.emit("case", 1.0, "tf=2")
        rows = bcommon.end_capture()
        bcommon.write_bench_json("t", rows)
        doc = json.loads((tmp_path / "BENCH_t.json").read_text())
        assert doc["telemetry"]["launches"] == {"gemm": 1}
        assert (tmp_path / "TRACE_t.json").exists()
        assert (tmp_path / "COUNTERS_t.json").exists()
        res = subprocess.run(
            [sys.executable, str(REPO / "tools" / "trace_check.py"),
             str(tmp_path)], capture_output=True, text=True)
        assert res.returncode == 0, res.stderr


# ---------------------------------------------------------------------------
# Engine + trainer integration
# ---------------------------------------------------------------------------

class TestEngineTrainerCounters:
    def test_paged_engine_counters_surface_in_capture(self):
        from repro.configs import get_config
        from repro.models import build_model
        from repro.serve.engine import PagedEngine, Request

        cfg = get_config("granite-8b", smoke=True)
        model = build_model(cfg, mode="reference")
        params = model.init(jax.random.PRNGKey(0))
        eng = PagedEngine(model, params, batch_slots=2, page_size=4,
                          max_pages_per_seq=4, n_pages=9)
        rng = np.random.default_rng(0)
        with obs.capture() as cap:
            for u in range(2):
                eng.submit(Request(u, rng.integers(0, cfg.vocab_size, 4)
                                   .astype(np.int32), 3))
            eng.run()
        assert cap.counter("engine.admissions") == eng.admissions == 2
        assert cap.counter("engine.tokens_generated") \
            == eng.tokens_generated == 6
        assert cap.counter("engine.peak_pages_in_use") \
            == eng.peak_pages_in_use > 0
        assert any(s.name == "engine.step" for s in cap.spans)
        assert any(s.name == "engine.sample" for s in cap.spans)
        rep = eng.report()
        assert rep["bucket_lru"]["misses"] >= 1

    def test_trainer_counters_surface_in_capture(self):
        with obs.capture() as cap:
            _train_tiny(3)
        assert cap.counter("trainer.steps") == 3
        assert cap.counter("trainer.bucket_pins") == 1
        assert cap.counter("trainer.bucket_pins.2x16") == 1
        steps = [s for s in cap.spans if s.name == "trainer.step"]
        assert len(steps) == 3 and all(s.dur > 0 for s in steps)

    def test_trainer_step_children(self):
        with obs.capture() as cap:
            _train_tiny(2)
        steps = [s for s in cap.spans if s.name == "trainer.step"]
        assert [s.meta for s in steps] == [{"step": 0}, {"step": 1}]
        for st in steps:
            assert st.parent is None
            kids = [s for s in cap.spans if s.parent is st]
            assert [k.name for k in kids] == [
                "trainer.data", "trainer.dispatch", "trainer.loss_read"]
            assert all(st.ts <= k.ts and k.ts + k.dur <= st.ts + st.dur
                       for k in kids)

    def test_engine_and_trainer_record_nothing_without_capture(self):
        obs.reset_null_allocations()
        eng = _serve_preempting()
        _train_tiny(2)
        assert eng.preemptions > 0
        assert obs.null_allocations() == 0
        assert eng._phase_start == {}

    def test_paged_engine_step_children(self):
        with obs.capture() as cap:
            eng = _serve_preempting()
        children = {"engine.admit", "engine.prefill_chunk", "engine.grow",
                    "engine.decode_launch", "engine.sample",
                    "engine.retire"}
        steps = [s for s in cap.spans if s.name == "engine.step"]
        assert steps and all(s.parent is None for s in steps)
        kids = [s for s in cap.spans if s.parent is not None
                and s.parent.name == "engine.step"]
        assert {k.name for k in kids} == children
        # every engine span is a step's child; intervals have no parent
        for sp in cap.spans:
            if sp.name in children:
                assert sp.parent.name == "engine.step"
        assert {k.rid for k in kids if k.name == "engine.prefill_chunk"} \
            == {0, 1}
        launches = [k for k in kids if k.name == "engine.decode_launch"]
        assert cap.counter("engine.decode_steps") == len(launches)
        assert cap.counter("engine.preemptions") == eng.preemptions > 0
        held = cap.counter("engine.kv.pages_held")
        assert 0 < cap.counter("engine.kv.tokens_held") \
            <= held * eng.page_size
        assert held <= len(launches) * (eng.n_pages - 1)

    def test_paged_engine_request_phases(self):
        """queue -> prefill -> decode per uid, contiguous; the preempted
        request's continuation queues again under the same rid, its phases
        flagged ``preempted``."""
        with obs.capture() as cap:
            eng = _serve_preempting()
        assert eng.preemptions == 1
        phases = {}
        for sp in cap.spans:
            if sp.name.startswith("engine.request."):
                assert sp.parent is None
                phases.setdefault(sp.rid, []).append(sp)
        assert set(phases) == {0, 1}
        want = {0: ["queue", "prefill", "decode"],
                1: ["queue", "prefill", "decode"] * 2}
        for uid, got in phases.items():
            got.sort(key=lambda sp: sp.ts)
            assert [sp.name.rsplit(".", 1)[1] for sp in got] == want[uid]
            for a, b in zip(got, got[1:]):
                assert a.ts + a.dur == pytest.approx(b.ts, abs=1e-9)
        flags = [bool(sp.meta and sp.meta.get("preempted"))
                 for sp in phases[1]]
        assert flags == [False] * 3 + [True] * 3
        assert not any(sp.meta for sp in phases[0])

    def test_paged_programs_are_named(self):
        """The device trace's XLA Modules line names the engine's programs
        (``jit_<name>``), not ``jit__lambda``."""
        from repro.serve.engine import PagedEngine
        cfg, model, params = _granite()
        eng = PagedEngine(model, params, batch_slots=2, page_size=4,
                          max_pages_per_seq=4, chunk_tokens=4,
                          draft_model=model, draft_params=params,
                          spec_tokens=2)
        pt, lens = eng.state["page_table"], eng.state["lengths"]
        toks = lambda *shape: jnp.zeros(shape, jnp.int32)
        programs = {
            "decode_step_paged": (eng._decode_bucket(1)["decode"],
                                  (toks(2, 1), eng.cache, pt[:, :1], lens)),
            "draft_decode_step_paged": (
                eng._decode_bucket(1, draft=True)["decode"],
                (toks(2, 1), eng.cache, pt[:, :1], lens)),
            "verify_step_paged": (eng._verify_bucket(1)["verify"],
                                  (toks(2, 2), eng.cache, pt[:, :1], lens)),
            "prefill_paged_chunk": (
                eng._chunk_bucket(4)["chunk"],
                (toks(1, 4), eng.cache, pt[0], jnp.int32(0), jnp.int32(3))),
            "prefill_paged": (eng._prefill_bucket(5)["prefill"],
                              (toks(1, 5), eng.cache, pt[0], 0, 5)),
        }
        for name, (fn, args) in programs.items():
            text = fn.lower(params, *args).as_text()
            assert text.startswith(f"module @jit_{name} "), text[:80]


def _granite():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("granite-8b", smoke=True)
    model = build_model(cfg, mode="reference")
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _serve_preempting():
    """Two 6-token prompts prefilled in 4-token chunks, 8 tokens each, over
    a 4-page pool: the younger is preempted once and finishes after."""
    from repro.serve.engine import PagedEngine, Request
    cfg, model, params = _granite()
    eng = PagedEngine(model, params, batch_slots=2, page_size=4,
                      max_pages_per_seq=4, n_pages=5, chunk_tokens=4)
    rng = np.random.default_rng(3)
    for u in range(2):
        eng.submit(Request(u, rng.integers(0, cfg.vocab_size, 6)
                           .astype(np.int32), 8))
    results = eng.run()
    assert sorted(results) == [0, 1]
    return eng


def _train_tiny(steps: int):
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, DataIterator
    from repro.models import build_model
    from repro.optim import AdamWConfig, cosine_schedule
    from repro.train import train_loop

    cfg = get_config("llama-100m")
    cfg = dataclasses.replace(cfg, num_layers=1, d_model=128,
                              num_heads=4, num_kv_heads=2, d_ff=256,
                              vocab_size=256, compute_dtype="float32")
    model = build_model(cfg, mode="reference")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                      global_batch=2)
    opt = AdamWConfig(schedule=cosine_schedule(1e-3, 1, 3))
    return train_loop(model, DataIterator(dcfg), steps, opt, log_every=0)
