"""Shared benchmark utilities.

Each benchmark prints ``name,us_per_call,derived`` CSV rows.
``us_per_call`` is a wall-clock measurement of the XLA-CPU reference path
(interpret-mode Pallas timings are not meaningful); ``derived`` carries
numbers from the analytic TPU-v5e model (TFLOP/s, hit-rates, bandwidths).
Neither is a device measurement: a modeled number is a hypothesis for a
chip run to check, and a CPU time says nothing about the chip
(``chip_smoke.py`` is the on-chip check).
"""
from __future__ import annotations

import json
import os
import time

import jax

from repro import obs

# When non-None, emit() also appends structured rows here (benchmarks.run
# uses this to write machine-readable BENCH_<key>.json artifacts next to
# the CSV stream, so the perf trajectory is diffable across commits).
_CAPTURE: list | None = None
# Telemetry capture bracketing the same window: begin_capture() opens an
# obs.capture(), end_capture() closes it and parks the recorder so
# write_bench_json() can embed the summary + export the trace files.
_OBS_CM = None
_LAST_REC: obs.Recorder | None = None


def begin_capture() -> None:
    global _CAPTURE, _OBS_CM, _LAST_REC
    _CAPTURE = []
    _OBS_CM = obs.capture()
    _LAST_REC = _OBS_CM.__enter__()


def end_capture() -> list:
    global _CAPTURE, _OBS_CM
    rows, _CAPTURE = _CAPTURE or [], None
    if _OBS_CM is not None:
        _OBS_CM.__exit__(None, None, None)
        _OBS_CM = None
    return rows


def last_recorder() -> obs.Recorder | None:
    """The telemetry recorder from the most recent capture window."""
    return _LAST_REC


def parse_derived(derived: str) -> dict:
    """'k=v;k2=v2' -> {k: float-or-str}; bare tokens keep their string."""
    out = {}
    for part in str(derived).split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            key, val = part.split("=", 1)
            try:
                out[key] = float(val.rstrip("x%"))
            except ValueError:
                out[key] = val
        else:
            out[part] = True
    return out


def write_bench_json(key: str, rows: list, out_dir: str | None = None) -> str:
    """Write BENCH_<key>.json (dir from $BENCH_OUT, default cwd).

    When a telemetry capture bracketed the bench (begin/end_capture), the
    journal summary is embedded as a ``telemetry`` block and the full trace
    is exported beside it as TRACE_<key>.json (Chrome-trace/Perfetto) and
    COUNTERS_<key>.json (flat counters + launch counts).
    """
    out_dir = out_dir or os.environ.get("BENCH_OUT", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{key}.json")
    payload = {"bench": key, "rows": rows}
    rec = _LAST_REC
    if rec is not None:
        payload["telemetry"] = rec.summary()
        obs.export_chrome_trace(rec, os.path.join(out_dir,
                                                  f"TRACE_{key}.json"))
        obs.export_counters(rec, os.path.join(out_dir,
                                              f"COUNTERS_{key}.json"))
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    return path


def measure_cell(fn, *args, warmup: int = 3, iters: int = 10) -> dict:
    """Measure one bench cell: wall-clock stats of ``fn(*args)``.

    The single timing loop every bench module shares — tests enforce that
    no bench module keeps a stray ``time.perf_counter`` loop of its own,
    so methodology changes (trimming, counter bracketing) land everywhere
    at once. ``warmup=0, iters=1`` is the one-shot path for side-effectful
    cells (e.g. an engine run that consumes its queue).

    Returns ``{"us": median microseconds, "seconds": median seconds,
    "min_us": best iteration, "iters": iters}``.
    """
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    med = times[len(times) // 2]
    return {"us": med * 1e6, "seconds": med, "min_us": times[0] * 1e6,
            "iters": len(times)}


def emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")
    if _CAPTURE is not None:
        _CAPTURE.append({"name": name, "us_per_call": round(us, 1),
                         "derived": str(derived),
                         "derived_parsed": parse_derived(derived)})


def gemm_candidate_sweep(shape: tuple):
    """The autotuner's GEMM candidate set for ``shape`` = (m, n, k), deduped
    by (block_m, block_n, block_k, n_buffers) — the swizzle axis moves DMA
    traffic, not the step model's TFLOPs. Yields (policy, selected: bool).
    Shared by bench_gemm and bench_schedules so their tables agree."""
    from repro.core import autotune

    sig = autotune.OpSignature("gemm", shape)
    chosen = autotune.select_policy("gemm", shape)
    chosen_key = (chosen.block_m, chosen.block_n, chosen.block_k,
                  chosen.n_buffers)
    seen = set()
    for pol in autotune.candidate_policies(sig):
        key = (pol.block_m, pol.block_n, pol.block_k, pol.n_buffers)
        if key in seen:
            continue
        seen.add(key)
        if key == chosen_key:
            # report the actually-selected policy (its swizzle included),
            # not whichever swizzle variant happened to come first
            yield chosen, True
        else:
            yield pol, False
