"""Run one cell once as ``run.py --trace 1`` does, with the program's own
spans on (``obs.capture(annotate=True)`` around the cell), and print what
they add: the cell's per-layer metrics, the span metrics
(``engine_queue_p90_ms``, ``prefill_p90_ms`` for serving,
``data_wait_ms.train`` for training), the idle gaps labelled "harness
annotation / program span / runtime event" and the idle time by program
span (``chipbench/spans.py``). Its difference from a plain traced run is
what the spans cost. The benchmark's own runs never run this.

    python3 chipbench/tools/span_run.py --workload granite-8b.chat \
        --seed 7 --seconds 51

Logs on stderr the longest ``engine.step`` and its children, the KV pool
held and filled per decode step, and the preemptions in the window (or the
longest ``trainer.step``). The last line of stdout is one JSON object.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import run  # noqa: E402


@dataclasses.dataclass
class SpanContext(run.Context):
    """The harness's context, also keeping the capture's counters as they
    stood when the window opened and closed."""
    rec: object = None
    at_open: dict = dataclasses.field(default_factory=dict)
    at_close: dict = dataclasses.field(default_factory=dict)

    def mark_setup(self):
        super().mark_setup()
        self.at_open = dict(self.rec.counters)

    def read_memory(self):
        super().read_memory()
        self.at_close = dict(self.rec.counters)


def span_metrics(kind: str, window, ctx) -> dict:
    from chipbench import spans
    rec, t0, t1 = ctx.rec, ctx.t_window, ctx.t_closed
    if kind == "train":
        run.log(spans.longest(rec, "trainer.step", t0, t1))
        return {"data_wait_ms.train": spans.data_wait_ms(rec, t0, t1)}
    run.log(spans.longest(rec, "engine.step", t0, t1))
    run.log(spans.kv_summary(ctx.at_open, ctx.at_close,
                             ctx.spec.raw["serve"]["page_size"]))
    return {"engine_queue_p90_ms": spans.engine_queue_p90_ms(window, rec),
            "prefill_p90_ms": spans.prefill_p90_ms(window, rec)}


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         mode: str = "pallas_tpu") -> int:
    """The command. The keywords are for tests, as ``run.main``'s; off the
    chip the harness's per-layer metrics are left out."""
    args = run.parse(argv)
    from chipbench import (correctness, model_spec, peaks, spans,
                           spec as bench_spec, trace_reduce, traffic)
    from repro import obs
    cell = bench_spec.cell(root, args.workload)
    spec = model_spec.load(cell.config_file)
    mix = traffic.load(cell.traffic_file)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    run.compile_cache(root)
    import jax
    dev = jax.devices()[0]
    if require_chip and dev.platform != "tpu":
        run.log(f"span_run: needs a TPU; found {dev.platform}")
        return 1
    trace_dir = os.path.join(root, ".chipbench_out", "span_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    with obs.capture(annotate=True) as rec:
        ctx = SpanContext(
            args.workload, spec, mix, args.seed, args.seconds, mode, dev,
            max(0.0, args.seconds - run.TRACE_SECONDS[mix["kind"]]),
            run.Tracer(trace_dir), run.CompileLog(), rec=rec)
        runner = importlib.import_module(
            f"chipbench.{run.KINDS[mix['kind']]}")
        out = runner.run(ctx)
    path = trace_reduce.find_file(trace_dir)
    tr = trace_reduce.reduce(path)
    st = spans.reduce(path, tr)
    shutil.rmtree(trace_dir, ignore_errors=True)
    ok, checks = correctness.judge(out["readings"],
                                   correctness.limits(root, args.workload))
    metrics = {}
    if require_chip:
        r = run.Run(spec, peaks.for_kind(dev.device_kind), out["window"],
                    tr, ctx.compiles.between(ctx.t_window, ctx.t_closed))
        for m in cell.per_layer:
            metrics[m["name"]] = bench_spec.metric_reader(root,
                                                          m["name"])(r)
    metrics.update(span_metrics(mix["kind"], out["window"], ctx))
    result = {"workload": args.workload, "seed": args.seed,
              "correct": bool(ok), "metrics": metrics,
              "device": {"kind": dev.device_kind, "busy_s": tr.busy_s,
                         "window_s": tr.window_s},
              "breakdown": {"device_ops": tr.top_ops(10),
                            "idle_gaps": st.gaps if st else tr.gaps[:10],
                            "idle_by_span": st.idle_by_span if st else {}},
              "checks": checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
