#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload granite-8b.chat --seed 7 \
        --seconds 51 --trace 0

The cell, its configuration, its traffic mix, its limits and its per-layer
metrics are found by name from BENCHMARK.json (``spec.py``). Set-up (model,
weights from the seed, warm-up) runs first; then the window measures for
``--seconds``; then the program's state is freed and what the window
produced is compared with the plain reference. With ``--trace 1`` the cell
runs under the program's own spans and counters
(``obs.capture(annotate=True)``), a device trace is taken over the end of
the window, and the per-layer metrics are reported in place of the
end-to-end ones; a plain run opens no capture.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device (and breakdown with a trace), and last the numbers
compared with their limits, which also close standard error. Without a
TPU, with fewer chips than the cell asks for, or outside a checkout of the
repository, it prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = {"serve_open_loop": 10.0, "train": 5.0}
KINDS = {"serve_open_loop": "serve_cell", "train": "train_cell"}
STEP_SPANS = {"serve_open_loop": "engine.step", "train": "trainer.step"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as fh:
            start = int(fh.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as fh:
            up = float(fh.read().split()[0])
        return up - start / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


class CompileLog:
    """Compiles and compile-cache loads, stamped, from JAX's monitoring
    events."""

    def __init__(self):
        import jax
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.perf_counter(), name))

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.events.append((time.perf_counter(), name))

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t, _ in self.events)


class Tracer:
    """The device trace of the end of the window, into a fixed directory
    of the checkout, without the Python tracer; ``on_edge`` is called with
    "trace_start" and "trace_stop"."""

    def __init__(self, directory: str):
        self.dir = directory
        self.on_edge = lambda edge: None
        self._ann = None

    def start(self):
        import jax
        from chipbench.trace_reduce import WINDOW
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        self.on_edge("trace_start")

    def stop(self):
        import jax
        self.on_edge("trace_stop")
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Context:
    """What a cell runner gets from the harness."""
    workload: str
    spec: object
    mix: dict
    seed: int
    seconds: float
    mode: str
    device: object
    trace_from: float | None
    tracer: Tracer | None
    compiles: CompileLog
    setup_s: float = -1.0
    t_window: float = 0.0
    t_closed: float = 0.0
    memory_peak: int = 0
    rec: object = None          # the obs recorder of a traced run
    counters_at: dict = dataclasses.field(default_factory=dict)

    def log(self, msg, *_, **__):
        log(f"[{self.workload}] {msg}")

    def snapshot(self, edge: str):
        """The program's counters as they stand at ``edge``."""
        if self.rec is not None:
            self.counters_at[edge] = dict(self.rec.counters)

    def counters(self, start: str, stop: str) -> dict:
        """Each counter's change from ``start`` to ``stop``."""
        a, b = self.counters_at.get(start), self.counters_at.get(stop)
        if a is None or b is None:
            return {}
        return {k: v - a.get(k, 0.0) for k, v in b.items()}

    def mark_setup(self):
        self.setup_s = process_age()
        self.t_window = time.perf_counter()
        self.snapshot("open")

    def read_memory(self):
        self.t_closed = time.perf_counter()
        self.snapshot("close")
        stats = self.device.memory_stats() or {}
        self.memory_peak = int(stats.get("peak_bytes_in_use", 0))


@dataclasses.dataclass
class Run:
    """What a per-layer metric reader gets: the configuration's sizes, the
    chip's peaks, the cell runner's record of the window, the reduced
    trace (with the program's spans in it), the compiles counted inside
    the window; the program's counters, each as its change over the
    window (``counters["window"]``) and over the traced part
    (``counters["traced"]``); the capture's recorder with every span and
    interval; and the window's open and close on its clock."""
    spec: object
    peak: object
    window: object
    trace: object
    window_compiles: int
    counters: dict = dataclasses.field(default_factory=dict)
    rec: object = None
    t_open: float = 0.0
    t_close: float = 0.0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def compile_cache(root: str) -> None:
    """JAX's persistent compile cache, always ``<root>/.jax_cache``: a
    fixed path inside the checkout, so a later run there finds it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    from repro.util import enable_compile_cache
    enable_compile_cache()


def main(argv=None, *, root: str = ROOT, require_chip: bool = True,
         mode: str = "pallas_tpu") -> int:
    """The command. The keywords are for tests: another tree, the CPU
    with another kernel mode."""
    args = parse(argv)
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        log("chipbench: no src/repro here; run from a checkout of the "
            "repository")
        return 2
    if root not in sys.path:
        sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "src"))
    from chipbench import model_spec, peaks, spec as bench_spec, traffic
    cell = bench_spec.cell(root, args.workload)
    spec = model_spec.load(cell.config_file, root)
    mix = traffic.load(cell.traffic_file)

    # the TPU runtime's logs go under the run's TMPDIR, not a fixed /tmp
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(),
                                                      "tpu_logs"))
    compile_cache(root)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu" or len(devices) < cell.chips):
        log(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
            f"found {len(devices)} {dev.platform} device(s)")
        return 1
    peak = peaks.for_kind(dev.device_kind) if require_chip else None

    trace_dir = os.path.join(root, ".chipbench_out", "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = Tracer(trace_dir) if args.trace else None
    trace_from = (max(0.0, args.seconds - TRACE_SECONDS[mix["kind"]])
                  if args.trace else None)
    import importlib
    runner = importlib.import_module(f"chipbench.{KINDS[mix['kind']]}")
    from repro import obs
    with (obs.capture(annotate=True) if args.trace
          else contextlib.nullcontext()) as rec:
        ctx = Context(args.workload, spec, mix, args.seed, args.seconds,
                      mode, dev, trace_from, tracer, CompileLog(), rec=rec)
        if tracer is not None:
            tracer.on_edge = ctx.snapshot
        out = runner.run(ctx)

    from chipbench import correctness
    ok, checks = correctness.judge(out["readings"],
                                   correctness.limits(root, args.workload))
    n_compiles = ctx.compiles.between(ctx.t_window, ctx.t_closed)
    late = getattr(out["window"], "late_s", None)
    if late:
        log(f"generator lateness: max {max(late) * 1e3:.3f} ms, mean "
            f"{sum(late) / len(late) * 1e3:.3f} ms over {len(late)}")
    log(f"compiles or cache loads inside the window: {n_compiles}; "
        f"set-up {ctx.setup_s:.3f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": ctx.memory_peak}
    result = {"correct": bool(ok),
              "attempted": out["attempted"], "failed": out["failed"]}
    metrics = {}
    if args.trace:
        from chipbench import spans, trace_reduce
        tr = trace_reduce.reduce(trace_reduce.find_file(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        counters = {"window": ctx.counters("open", "close"),
                    "traced": ctx.counters("trace_start", "trace_stop")}
        log(spans.longest(rec, STEP_SPANS[mix["kind"]], ctx.t_window,
                          ctx.t_closed))
        if mix["kind"] == "serve_open_loop":
            log(spans.kv_summary(ctx.counters_at.get("open", {}),
                                 ctx.counters_at.get("close", {}),
                                 spec.raw["serve"]["page_size"]))
        log(f"idle by program span: {tr.idle_by_span}")
        run = Run(spec, peak, out["window"], tr, n_compiles, counters, rec,
                  ctx.t_window, ctx.t_closed)
        for m in cell.per_layer:
            value = bench_spec.metric_reader(root, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.gaps[:10]}
    else:
        values = dict(out["e2e"], setup_s=ctx.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
