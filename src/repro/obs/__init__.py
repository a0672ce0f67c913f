"""Kernel-launch telemetry: launch journal, spans, counters, plan audit.

Zero-dependency observability for the whole stack (DESIGN.md §13). The
subsystem is compiled-in everywhere — every kernel entry point, the
autotuner, the serving engine, and the trainer call into this module
unconditionally — but the *disabled* path is a guarded no-op: each public
recording function's first action is a plain attribute check against the
module-level recorder stack, and no event object, dict, or formatted
string is constructed unless a recorder is active. ``null_allocations()``
is the tripwire that proves it: the internal allocation helpers bump it
if they ever run with no active recorder, so tests can assert the null
path allocated exactly nothing.

Usage (the sanctioned replacement for monkeypatch launch counting):

    from repro import obs
    with obs.capture() as cap:
        y = model(x)
    assert cap.count("gemm_fused") == 2
    obs.export_chrome_trace(cap, "trace.json")

Four record types share one Recorder:

- ``LaunchEvent``  — one per kernel-entry Python call (trace/dispatch
  semantics: a jitted caller re-using its cache emits nothing, exactly
  like the old monkeypatch counters).
- ``SpanEvent``    — host intervals on ``time.perf_counter()``: spans
  (``obs.span``) nest on a per-thread stack, so each knows its ``parent``
  and inherits its request id ``rid``; ``obs.interval`` records a request
  phase whose start lay in an earlier call.
- counters        — monotonic floats (``obs.incr``), exported flat.
- ``PlanDecision`` — every ``select_policy``/``select_fusion`` verdict
  with the losing candidates and their modeled bytes.

``capture(annotate=True)`` also opens a ``jax.profiler.TraceAnnotation``
for each span, named ``repro.<span>`` and carrying ``rid`` as a stat, so
that a profiler trace taken meanwhile holds the program's spans on the
clock of the device ops: in an ``.xplane.pb`` the program's spans are the
host events whose name starts with ``PROFILER_PREFIX``.

Exporters emit Chrome-trace/Perfetto JSON (``traceEvents``) and a flat
counters JSON; both are validated by ``tools/trace_check.py`` in CI.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any

__all__ = [
    "LaunchEvent", "SpanEvent", "PlanDecision", "Recorder",
    "capture", "enabled", "launch", "incr", "span", "interval",
    "plan_decision", "PROFILER_PREFIX", "null_allocations",
    "reset_null_allocations",
    "export_chrome_trace", "export_counters", "chrome_trace_events",
]


# ---------------------------------------------------------------------------
# Event records
# ---------------------------------------------------------------------------

@dataclass
class LaunchEvent:
    """One kernel-entry call. ``dma_bytes``/``flops`` are the analytic
    perf_model numbers the caller already had in hand (never recomputed
    here). Kernel time comes from a device trace, not from here."""
    op: str                       # journal op kind, e.g. "gemm_fused"
    variant: str = ""             # free-form: "da", "paged", "prenorm", ...
    grid: tuple | None = None
    policy: dict | None = None    # KernelPolicy.describe() payload
    chain: str | None = None      # chain-spec summary (epilogue/prologue)
    dma_bytes: int | None = None
    flops: int | None = None
    ts: float = 0.0               # perf_counter seconds at record time


@dataclass
class SpanEvent:
    name: str
    ts: float                     # begin, perf_counter seconds
    dur: float = 0.0              # seconds
    # the span open around this one on its thread when it began
    parent: SpanEvent | None = field(default=None, repr=False,
                                     compare=False)
    rid: int | None = None        # request id; None outside a request
    meta: dict | None = None


@dataclass
class PlanDecision:
    """One autotuner verdict. ``kind`` is "policy" (select_policy),
    "fusion" (select_fusion), or "bwd_route" (select_bwd_mode — the
    bwd_mode='auto' kernel-vs-oracle routing); ``candidates`` lists every
    scored loser with its modeled time/bytes so the choice is explainable
    after the fact. ``cached`` marks a memo replay (same decision, zero
    rescoring)."""
    kind: str
    op: str
    shape: tuple
    dtype: str
    chosen: Any
    candidates: list = field(default_factory=list)
    cached: bool = False
    ts: float = 0.0

    def to_json(self) -> dict:
        return {"kind": self.kind, "op": self.op, "shape": list(self.shape),
                "dtype": self.dtype, "chosen": self.chosen,
                "candidates": self.candidates, "cached": self.cached,
                "ts": self.ts}


# ---------------------------------------------------------------------------
# Recorder + module state
# ---------------------------------------------------------------------------

class Recorder:
    """Accumulates events for one ``capture()`` window."""

    def __init__(self, *, annotate: bool = False):
        # the profiler sink: jax.profiler.TraceAnnotation, or None
        self.sink = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self.sink = TraceAnnotation
        self.launches: list[LaunchEvent] = []
        self.spans: list[SpanEvent] = []
        self.counters: dict[str, float] = {}
        self.plans: list[PlanDecision] = []

    # -- queries ------------------------------------------------------------
    def count(self, op: str | None = None, variant: str | None = None) -> int:
        """Number of journal launches matching ``op`` (and ``variant``)."""
        n = 0
        for e in self.launches:
            if op is not None and e.op != op:
                continue
            if variant is not None and e.variant != variant:
                continue
            n += 1
        return n

    def launch_counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for e in self.launches:
            out[e.op] = out.get(e.op, 0) + 1
        return out

    def modeled_bytes(self, op: str | None = None) -> int:
        """Sum of journal-carried modeled dma_bytes (op-filtered)."""
        return sum(e.dma_bytes or 0 for e in self.launches
                   if op is None or e.op == op)

    def counter(self, name: str) -> float:
        return self.counters.get(name, 0.0)

    def plans_of(self, kind: str) -> list:
        """Plan decisions of one kind ('policy' | 'fusion' | 'bwd_route'),
        in journal order."""
        return [p for p in self.plans if p.kind == kind]

    def summary(self) -> dict:
        """The ``telemetry`` block embedded in BENCH_<key>.json."""
        return {
            "launches": self.launch_counts(),
            "modeled_dma_bytes": {
                op: self.modeled_bytes(op) for op in self.launch_counts()},
            "counters": dict(sorted(self.counters.items())),
            "plan_decisions": len(self.plans),
            "spans": len(self.spans),
        }


class _State(threading.local):
    def __init__(self):
        self.stack: list[Recorder] = []
        self.open: list[SpanEvent] = []     # spans open on this thread


_STATE = _State()
_LOCK = threading.Lock()
_NULL_ALLOCS = 0          # bumped only if an event is built while disabled
PROFILER_PREFIX = "repro."
_now = time.perf_counter


def enabled() -> bool:
    """True when at least one ``capture()`` window is active (this thread)."""
    return bool(_STATE.stack)


def null_allocations() -> int:
    """How many event objects were built with no recorder active. The
    zero-overhead contract (DESIGN.md §13) is that this stays 0: every
    recording helper returns before allocating when disabled."""
    return _NULL_ALLOCS


def reset_null_allocations() -> None:
    global _NULL_ALLOCS
    with _LOCK:
        _NULL_ALLOCS = 0


def _record(ev: LaunchEvent | SpanEvent) -> None:
    global _NULL_ALLOCS
    s = _STATE.stack
    if not s:                       # tripwire: caller skipped the guard
        with _LOCK:
            _NULL_ALLOCS += 1
        return
    for rec in s:
        (rec.launches if isinstance(ev, LaunchEvent)
         else rec.spans).append(ev)


# ---------------------------------------------------------------------------
# Recording API (every function's first line is the disabled-path guard)
# ---------------------------------------------------------------------------

def launch(op: str, *, variant: str = "", grid=None, policy=None,
           chain=None, dma_bytes=None, flops=None) -> None:
    """Journal one kernel-entry call. ``policy`` may be a KernelPolicy
    (its ``describe()`` runs lazily, only here) or an already-built dict."""
    if not _STATE.stack:
        return
    if policy is not None and not isinstance(policy, dict):
        describe = getattr(policy, "describe", None)
        policy = describe() if describe else {"policy": str(policy)}
    if grid is not None:
        grid = tuple(grid)
    _record(LaunchEvent(op=op, variant=variant, grid=grid, policy=policy,
                        chain=chain, dma_bytes=dma_bytes, flops=flops,
                        ts=_now()))


def incr(name: str, value: float = 1.0) -> None:
    """Bump a monotonic counter in every active recorder."""
    s = _STATE.stack
    if not s:
        return
    for rec in s:
        rec.counters[name] = rec.counters.get(name, 0.0) + value


def gauge(name: str, value: float) -> None:
    """Record the running max of a value (peak occupancy and friends)."""
    s = _STATE.stack
    if not s:
        return
    for rec in s:
        if value > rec.counters.get(name, float("-inf")):
            rec.counters[name] = value


@contextmanager
def span(name: str, *, rid: int | None = None, **meta):
    """Host interval: ``with obs.span("engine.sample", rid=7): ...``. Its
    parent is the span open around it on this thread, whose ``rid`` it
    inherits unless given one. Free when disabled: no timestamps are
    taken, no event is built."""
    st = _STATE
    if not st.stack:
        yield
        return
    parent = st.open[-1] if st.open else None
    if rid is None and parent is not None:
        rid = parent.rid
    sink = next((r.sink for r in st.stack if r.sink is not None), None)
    ann = None
    if sink is not None:
        ann = (sink(PROFILER_PREFIX + name) if rid is None
               else sink(PROFILER_PREFIX + name, rid=rid))
        ann.__enter__()
    ev = SpanEvent(name, _now(), parent=parent, rid=rid, meta=meta or None)
    st.open.append(ev)
    try:
        yield
    finally:
        ev.dur = _now() - ev.ts
        st.open.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        _record(ev)


def interval(name: str, t0: float, t1: float, *, rid: int | None = None,
             **meta) -> None:
    """An interval with given ``perf_counter`` bounds, such as a request
    phase that began in an earlier call. Kept in the recorders only: it
    has no parent and reaches no profiler trace."""
    s = _STATE.stack
    if not s:
        return
    _record(SpanEvent(name, t0, t1 - t0, rid=rid, meta=meta or None))


def plan_decision(kind: str, op: str, shape, dtype: str, chosen,
                  candidates=None, cached: bool = False) -> None:
    """Audit one autotuner verdict (select_policy / select_fusion)."""
    s = _STATE.stack
    if not s:
        return
    ev = PlanDecision(kind=kind, op=op, shape=tuple(shape), dtype=dtype,
                      chosen=chosen, candidates=list(candidates or []),
                      cached=cached, ts=_now())
    for rec in s:
        rec.plans.append(ev)


@contextmanager
def capture(*, annotate: bool = False):
    """Activate a fresh Recorder for the dynamic extent of the block and
    yield it. Nested captures each see every event recorded inside them
    (events fan out to the whole stack). With ``annotate`` every span is
    also a ``jax.profiler.TraceAnnotation`` (the module docstring)."""
    rec = Recorder(annotate=annotate)
    _STATE.stack.append(rec)
    try:
        yield rec
    finally:
        _STATE.stack.remove(rec)


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

_PID = 1
_TID_LAUNCH = 1   # kernel-launch journal track
_TID_SPAN = 2     # span track


def chrome_trace_events(rec: Recorder) -> list[dict]:
    """Flatten a Recorder into Chrome-trace ``traceEvents`` (Perfetto
    opens these directly), timed from the recorder's first event. Launches
    are instant events ('i'); spans are 'X' with their ``rid`` in
    ``args``; counters land as one final 'C' sample per series."""
    stamps = ([e.ts for e in rec.launches] + [sp.ts for sp in rec.spans]
              + [p.ts for p in rec.plans])
    zero = min(stamps, default=0.0)
    events: list[dict] = []
    for e in rec.launches:
        args: dict[str, Any] = {}
        for k in ("variant", "chain", "dma_bytes", "flops"):
            v = getattr(e, k)
            if v not in (None, ""):
                args[k] = v
        if e.grid is not None:
            args["grid"] = list(e.grid)
        if e.policy is not None:
            args["policy"] = e.policy
        events.append({"name": e.op, "cat": "launch", "ph": "i", "s": "t",
                       "pid": _PID, "tid": _TID_LAUNCH,
                       "ts": (e.ts - zero) * 1e6, "args": args})
    for sp in rec.spans:
        args = dict(sp.meta or {})
        if sp.rid is not None:
            args["rid"] = sp.rid
        events.append({"name": sp.name, "cat": "span", "ph": "X",
                       "pid": _PID, "tid": _TID_SPAN,
                       "ts": (sp.ts - zero) * 1e6, "dur": sp.dur * 1e6,
                       "args": args})
    t_end = max([e.ts for e in rec.launches]
                + [sp.ts + sp.dur for sp in rec.spans] + [zero])
    for name, value in sorted(rec.counters.items()):
        events.append({"name": name, "cat": "counter", "ph": "C",
                       "pid": _PID, "ts": (t_end - zero) * 1e6,
                       "args": {"value": value}})
    return events


def export_chrome_trace(rec: Recorder, path) -> str:
    """Write Perfetto-loadable Chrome trace JSON; returns the path."""
    doc = {"traceEvents": chrome_trace_events(rec),
           "displayTimeUnit": "ms",
           "otherData": {"producer": "repro.obs",
                         "plan_decisions": [p.to_json() for p in rec.plans]}}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return str(path)


def export_counters(rec: Recorder, path) -> str:
    """Write the flat counters JSON (stable sorted keys); returns path."""
    doc = {"counters": dict(sorted(rec.counters.items())),
           "launches": rec.launch_counts()}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return str(path)
