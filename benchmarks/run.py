"""Run every benchmark (one per paper table/figure).

Prints ``name,us_per_call,derived`` CSV. us_per_call is the measured XLA-CPU
reference path; derived carries numbers from the analytic TPU-v5e model —
neither is a device measurement (see benchmarks/common.py).

Each bench also writes a machine-readable ``BENCH_<key>.json`` (rows +
parsed derived fields + a ``telemetry`` block from the launch journal;
directory from ``$BENCH_OUT``, default cwd) so the perf trajectory can be
tracked across commits — CI uploads them as artifacts. Beside each bench
JSON land ``TRACE_<key>.json`` (Chrome-trace/Perfetto, load at
https://ui.perfetto.dev) and ``COUNTERS_<key>.json`` (flat counters),
validated in CI by ``tools/trace_check.py``.
"""
from __future__ import annotations

import sys
import traceback

from repro.util import enable_compile_cache

from . import (bench_gemm, bench_attention_fwd, bench_attention_bwd,
               bench_attention_fusion, bench_calibration, bench_decode,
               bench_distributed, bench_fused_mlp, bench_memory_bound,
               bench_schedules, bench_grid_swizzle, bench_serve)
from .common import begin_capture, end_capture, write_bench_json

# (display name, json key, entry point)
BENCHES = [
    ("Fig6_gemm", "gemm", bench_gemm.main),
    ("Fig7_attention_fwd", "attention_fwd", bench_attention_fwd.main),
    ("Fig8_attention_bwd", "attention_bwd", bench_attention_bwd.main),
    ("Fig7b_attention_fusion", "attention_fusion",
     bench_attention_fusion.main),
    ("Fig9_memory_bound", "memory_bound", bench_memory_bound.main),
    ("Fig9b_decode", "decode", bench_decode.main),
    ("Fig9c_fused_mlp", "fused_mlp", bench_fused_mlp.main),
    ("Tab2_Tab3_schedules", "schedules", bench_schedules.main),
    ("Tab4_grid_swizzle", "grid_swizzle", bench_grid_swizzle.main),
    ("Serve_fastpath", "serve", bench_serve.main),
    ("Sec16_distributed", "distributed", bench_distributed.main),
    ("Sec6_calibration", "calibration", bench_calibration.main),
]


def main() -> None:
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, key, fn in BENCHES:
        print(f"# --- {name} ---")
        begin_capture()
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
        finally:
            path = write_bench_json(key, end_capture())
            print(f"# wrote {path}")
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
