"""What the program's names and spans add to a traced run
(``chipbench/spans.py``): module executions and the two device readers on
hand-built traces, the span readers on a hand-built window and recorder,
the reduction on a trace recorded on one TPU v5e with the spans on
(``tools/record_span_fixture.py``: granite-8b at one layer serving three
requests with 512-token chunks), and ``run.py --trace 1`` on the CPU,
with a metric file of a temporary tree reading a program counter."""
import json
import os

import numpy as np
import pytest

from chipbench import serving, spans, trace_reduce
from chipbench import spec as bench_spec
from chipbench.run import Run
from chipbench.tests import tree
from repro.obs import Recorder, SpanEvent

DATA = os.path.join(os.path.dirname(__file__), "data")
SPANS = os.path.join(DATA, "serve_spans.xplane.pb.gz")


def _trace(modules):
    """A reduced trace of one device running ``modules`` back to back:
    (module, [op durations in ns]) each, with a 1 us gap between modules
    and none between ops."""
    ops, t = [], 0.0
    for module, durs in modules:
        for d in durs:
            ops.append(trace_reduce.Op("%op = f32[1] fusion()", t, d,
                                       module))
            t += d
        t += 1000.0
    return trace_reduce.Trace(t * 1e-9, t * 1e-9, 1, ops, [])


DECODE = "jit_decode_step_paged(11)"
CHUNK = "jit_prefill_paged_chunk(22)"
PROGRAM_TRACE = [(DECODE, [5e6]),                  # cut by the window
                 ("jit_add(3)", [500.0]), (CHUNK, [3e6, 4e6]),
                 ("jit_scatter(4)", [500.0]), (CHUNK, [1e6, 2e6]),
                 ("jit_add(3)", [500.0]), (DECODE, [6e6, 1e6, 1e6]),
                 ("jit_argmax(5)", [500.0]), ("jit_prefill_paged(6)", [9e6]),
                 (DECODE, [2e6])]                  # cut by the window


def test_executions_are_runs_of_one_named_module():
    t = _trace(PROGRAM_TRACE)
    assert spans.executions(t, "prefill_paged_chunk") == pytest.approx(
        [7e-3, 3e-3])
    # the first and the last run of the window are left out
    assert spans.executions(t, "decode_step_paged") == pytest.approx([8e-3])
    # a name is matched whole, not as a prefix
    assert spans.executions(t, "prefill_paged") == pytest.approx([9e-3])
    assert spans.executions(t, "prefill") == []


def _run(trace):
    return Run(None, None, None, trace, 0)


def test_device_readers_on_hand_built_runs():
    decode = bench_spec.metric_reader(tree.REPO, "decode_device_ms.serve")
    chunk = bench_spec.metric_reader(tree.REPO, "chunk_device_ms.serve")
    t = _trace(PROGRAM_TRACE)
    assert decode(_run(t)) == pytest.approx(8.0)
    assert chunk(_run(t)) == pytest.approx(5.0)
    # a program whose jitted steps are lambdas: nothing to read
    anon = _trace([(m.replace("decode_step_paged", "_lambda")
                    .replace("prefill_paged_chunk", "_lambda"), d)
                   for m, d in PROGRAM_TRACE])
    assert decode(_run(anon)) is None and chunk(_run(anon)) is None
    assert decode(_run(None)) is None


def _window(t0, t1, reqs):
    return serving.Window(t0, t1, reqs, reqs, [], [], 4, 32)


def _req(uid, due, submitted):
    return serving.ReqRecord(uid, due, 8, 4, submitted=submitted)


def test_request_phase_readers_on_hand_built_window():
    rec = Recorder()
    phases = [  # rid, phase, start, end, preempted
        (1, "queue", 10.0, 10.5, False), (1, "prefill", 10.5, 12.0, False),
        (1, "decode", 12.0, 13.0, False),
        (1, "queue", 13.0, 19.0, True), (1, "prefill", 19.0, 21.0, True),
        (2, "queue", 11.0, 11.2, False), (2, "prefill", 11.2, 25.0, False),
        (3, "queue", 14.0, 16.0, False),         # admitted, no first token
        (9, "queue", 1.0, 9.0, False)]           # the lead's: not due
    for rid, ph, a, b, pre in phases:
        rec.spans.append(SpanEvent(f"engine.request.{ph}", a, b - a,
                                   rid=rid,
                                   meta={"preempted": True} if pre else None))
    reqs = {u: _req(u, due, sub) for u, due, sub in [
        (1, 9.9, 10.0), (2, 11.0, 11.0), (3, 14.0, 14.0), (4, 17.0, 17.5),
        (5, 20.0, -1.0)]}
    w = _window(9.0, 20.0, reqs)
    # queue: 0.5, 0.2, 2.0, 2.5 (not admitted: submit to close), 0 (never
    # submitted); prefill: 1.5, 8.8 (cut at the close), 4.0 (admitted, no
    # first token), 0, 0
    assert spans.engine_queue_p90_ms(w, rec) == pytest.approx(
        np.percentile([0.5, 0.2, 2.0, 2.5, 0.0], 90) * 1e3)
    assert spans.prefill_p90_ms(w, rec) == pytest.approx(
        np.percentile([1.5, 8.8, 4.0, 0.0, 0.0], 90) * 1e3)
    assert spans.engine_queue_p90_ms(_window(9.0, 20.0, {}), rec) is None


def test_trainer_and_engine_summaries():
    rec = Recorder()
    step = SpanEvent("trainer.step", 5.0, 2.0)
    rec.spans += [SpanEvent("trainer.data", 1.0, 0.5),          # set-up
                  SpanEvent("trainer.data", 5.0, 0.004, parent=step),
                  SpanEvent("trainer.dispatch", 5.004, 0.01, parent=step),
                  step, SpanEvent("trainer.data", 7.0, 0.002)]
    assert spans.data_wait_ms(rec, 4.0, 9.0) == pytest.approx(3.0)
    assert spans.data_wait_ms(rec, 10.0, 11.0) is None
    line = spans.longest(rec, "trainer.step", 4.0, 9.0)
    assert line.startswith("longest trainer.step 2000.000 ms at 1.000 s")
    assert "trainer.data 4.000 ms, trainer.dispatch 10.000 ms" in line
    at_open = {"engine.decode_steps": 10, "engine.kv.pages_held": 100,
               "engine.kv.tokens_held": 4000, "engine.preemptions": 1}
    at_close = {"engine.decode_steps": 20, "engine.kv.pages_held": 300,
                "engine.kv.tokens_held": 10400, "engine.preemptions": 3}
    assert spans.kv_summary(at_open, at_close, 64) == (
        "KV pool: 20.0 pages held per decode step, 50.0% filled; "
        "2 preemptions")
    assert spans.kv_summary(at_open, at_open, 64).startswith("no decode")


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce(SPANS)


def test_fixture_module_executions_match_the_modules_line(recorded):
    tr = recorded
    modules = {}
    for plane in trace_reduce.load(SPANS).planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") \
                    and line.name == "XLA Modules":
                for ev in line.events:
                    modules.setdefault(ev.name.split("(")[0], []).append(
                        ev.duration_ns * 1e-9)
    for program in ("decode_step_paged", "prefill_paged_chunk"):
        got = spans.executions(tr, program)
        want = modules[f"jit_{program}"]
        # all but the window's first and last run of the module
        assert len(want) - 2 <= len(got) <= len(want)
        # first op to last: the module's event less its few microseconds
        # of launch (0.2-1.6 us here)
        for g in got:
            assert min(abs(g - w) for w in want) < 5e-6
    assert "jit__lambda" not in modules


def test_fixture_gaps_name_program_spans(recorded):
    tr = recorded
    assert tr.spans
    assert {name for _, _, name, _ in tr.spans} >= {
        "engine.step", "engine.admit", "engine.prefill_chunk",
        "engine.grow", "engine.decode_launch", "engine.sample",
        "engine.retire"}
    assert {rid for _, _, name, rid in tr.spans
            if name == "engine.prefill_chunk"} == {10, 11, 12}
    assert len(tr.gaps) == 10
    for label, seconds in tr.gaps:
        harness, program, runtime = label.split(" / ")
        assert seconds > 0 and runtime
        assert program == trace_reduce.OUTSIDE or program.startswith(
            "engine.")
    # the longest gaps of the window, with the program span between
    assert sum(g for _, g in tr.gaps) <= tr.window_s - tr.busy_s + 1e-9
    assert [g for _, g in tr.gaps] == sorted((g for _, g in tr.gaps),
                                             reverse=True)
    assert any(label.split(" / ")[1] != trace_reduce.OUTSIDE
               for label, _ in tr.gaps)


def test_fixture_idle_by_span_adds_up_to_idle(recorded):
    tr = recorded
    assert sum(tr.idle_by_span.values()) == pytest.approx(
        tr.window_s - tr.busy_s, rel=1e-9, abs=1e-9)
    assert all(s > 0 for s in tr.idle_by_span.values())
    top = next(iter(tr.idle_by_span))
    assert top.startswith("engine.")
    # a trace without program spans puts every idle second outside them
    plain = trace_reduce.reduce(os.path.join(DATA, "serve.xplane.pb.gz"))
    assert plain.spans == []
    assert list(plain.idle_by_span) == [trace_reduce.OUTSIDE]
    assert plain.idle_by_span[trace_reduce.OUTSIDE] == pytest.approx(
        plain.window_s - plain.busy_s, rel=1e-9, abs=1e-9)


COUNTER_METRIC = """
def read(run):
    walked = run.counters["window"].get("engine.kv.blocks_walked")
    return float(walked) if walked else None
"""
_TRACED: dict = {}


def _traced(tmp_path_factory, workload):
    """``run.main --trace 1`` of a tiny cell on the CPU, in a tree whose
    ``metrics/`` gains ``kv_blocks_walked.py``, a reader of a program
    counter, entered for the serving cell; the metrics that need the
    chip's peaks are left out."""
    if workload not in _TRACED:
        root = tree.build(str(tmp_path_factory.mktemp("traced")))
        tree.add_files(root, "metrics",
                       {"kv_blocks_walked.py": COUNTER_METRIC})
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as fh:
            bench = json.load(fh)
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if not m["name"].startswith("mfu.")]
        bench["per_layer"].append({
            "name": "kv_blocks_walked", "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "engine",
            "moves": "itl_p95_ms", "workloads": ["tiny-lm.chat"]})
        with open(path, "w") as fh:
            json.dump(bench, fh)
        _TRACED[workload] = tree.run_cell(root, workload,
                                          seed=2 ** 33 + 5, trace=1,
                                          timeout=600)
    return _TRACED[workload]


@pytest.mark.parametrize("workload,names", [
    ("tiny-lm.chat", {"engine_queue_p90_ms", "prefill_p90_ms"}),
    ("tiny-cpm.train", {"data_wait_ms.train"})])
def test_span_run_on_cpu(tmp_path_factory, workload, names):
    rc, res, err = _traced(tmp_path_factory, workload)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    assert names <= set(res["metrics"])
    assert all(res["metrics"][n]["value"] >= 0 for n in names)
    gaps = res["breakdown"]["idle_gaps"]
    assert gaps and all(len(label.split(" / ")) == 3 for label, _ in gaps)
    by_span = err.split("idle by program span: ", 1)[1].splitlines()[0]
    assert "'engine." in by_span or "'trainer." in by_span
    step = "engine.step" if "chat" in workload else "trainer.step"
    assert f"longest {step} " in err


def test_counter_metric_from_added_file(tmp_path_factory):
    rc, res, err = _traced(tmp_path_factory, "tiny-lm.chat")
    assert rc == 0, err[-3000:]
    walked = res["metrics"]["kv_blocks_walked"]
    assert walked["unit"] == "count" and walked["value"] >= 1
    # the engine's own count over the window, as the KV line reports it
    assert "KV pool: " in err
