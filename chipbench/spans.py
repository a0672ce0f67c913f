"""What the program's own names and spans add to a traced run.

The program names its jitted serving programs (``decode_step_paged``,
``prefill_paged_chunk``, ...), so a device trace's ``XLA Modules`` line
tells them apart: ``executions`` gives the device time of each execution
of one of them from the reduced trace alone.

Under ``obs.capture(annotate=True)`` the program's spans (``engine.*``,
``trainer.*``) are also host events of the profiler trace, named with
``PREFIX``. ``reduce`` reads them beside the device ops: it labels each
idle gap "harness annotation / program span / runtime event" and sums the
device's idle time by the innermost program span. The readers below it
take the recorder of such a capture: the engine's request phases
(``engine.request.*`` intervals) and the trainer's ``trainer.data`` spans.
``tools/span_run.py`` runs a cell so.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import trace_reduce

PREFIX = "repro."           # the program's spans in a profiler trace
OUTSIDE = "outside the program spans"


def executions(trace, program: str) -> list:
    """Device seconds of each execution of the jitted program ``program``
    (the module ``jit_<program>(<fingerprint>)``) in the traced window of
    one device: from its first op's start to its last op's end, an
    execution being a run of that module's ops with no other module's op
    between. The window's first and last runs are left out, since the
    window may cut them."""
    want = f"jit_{program}"
    runs = []                   # [module, start_ns, end_ns]
    for op in sorted(trace.ops, key=lambda o: o.start_ns):
        if not op.module:
            continue
        end = op.start_ns + op.dur_ns
        if runs and runs[-1][0] == op.module:
            runs[-1][2] = max(runs[-1][2], end)
        else:
            runs.append([op.module, op.start_ns, end])
    return [(e - s) * 1e-9 for m, s, e in runs[1:-1]
            if m.split("(", 1)[0] == want]


def mean_ms(seconds: list):
    return 1e3 * sum(seconds) / len(seconds) if seconds else None


@dataclasses.dataclass
class SpanTrace:
    spans: list             # (start_ns, end_ns, name, rid) in the window
    gaps: list              # [label, seconds], longest first
    idle_by_span: dict      # innermost program span -> idle seconds


def _inner(events, t: float):
    """Name of the innermost event (latest start) holding ``t``."""
    best = None
    for ev in events:
        if ev[0] <= t <= ev[1] and (best is None or ev[0] >= best[0]):
            best = ev
    return best[2] if best else None


def reduce(path: str, trace, n_gaps: int = 10) -> SpanTrace | None:
    """The program spans of the profile at ``path`` against ``trace``
    (``trace_reduce.reduce`` of the same file); None when the profile
    holds no program span."""
    window, own, program, runtime = None, [], [], []
    for plane in trace_reduce.load(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == trace_reduce.WINDOW:
                    window = (s, e)
                elif ev.name.startswith(trace_reduce.OWN_PREFIX):
                    own.append((s, e, ev.name))
                elif ev.name.startswith(PREFIX):
                    program.append((s, e, ev.name[len(PREFIX):],
                                    dict(ev.stats).get("rid")))
                elif not ev.name.startswith("$"):
                    runtime.append((s, e, ev.name))
    if window is None or not program:
        return None
    w0, w1 = window
    program = sorted(sp for sp in program if sp[1] > w0 and sp[0] < w1)
    busy = trace_reduce._union([(op.start_ns, op.start_ns + op.dur_ns)
                                for op in trace.ops])
    gaps, prev = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, min(s, w1)))
        prev = max(prev, e)
    idle: dict = {}
    for s, e in gaps:
        inside = [sp for sp in program if sp[1] > s and sp[0] < e]
        cuts = sorted({s, e} | {t for sp in inside for t in sp[:2]
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            name = _inner(inside, (a + b) / 2) or OUTSIDE
            idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = []
    for s, e in gaps[:n_gaps]:
        mid = (s + e) / 2
        parts = (_inner(own, mid) or "outside the harness's annotations",
                 _inner(program, mid) or OUTSIDE,
                 _inner(runtime, mid) or "host Python")
        labelled.append([" / ".join(parts), (e - s) * 1e-9])
    return SpanTrace(program, labelled,
                     dict(sorted(idle.items(), key=lambda kv: -kv[1])))


# -- readers of a capture's recorder ---------------------------------------

def _first_phase(rec, phase: str) -> dict:
    """rid -> the first ``engine.request.<phase>`` interval of a request
    (a preempted continuation's phases are left out)."""
    name = f"engine.request.{phase}"
    out: dict = {}
    for sp in sorted(rec.spans, key=lambda sp: sp.ts):
        if sp.name == name and not (sp.meta or {}).get("preempted"):
            out.setdefault(sp.rid, sp)
    return out


def engine_queue_p90_ms(window, rec):
    """p90, over the requests due in the serving window, of the engine's
    queue phase (submit to first admission) cut at the close; one not
    admitted by the close counts from its submission to the close."""
    queue = _first_phase(rec, "queue")
    waits = []
    for uid, r in window.requests.items():
        iv = queue.get(uid)
        if iv is not None:
            waits.append(min(iv.ts + iv.dur, window.t1) - iv.ts)
        else:
            waits.append(window.t1 - r.submitted if r.submitted >= 0
                         else 0.0)
    return float(np.percentile(waits, 90)) * 1e3 if waits else None


def prefill_p90_ms(window, rec):
    """p90, over the same requests, of the prefill phase (admission to
    first token) cut at the close; one admitted but without a first token
    by the close counts from its admission to the close, one not admitted
    counts 0."""
    queue, prefill = _first_phase(rec, "queue"), _first_phase(rec, "prefill")
    times = []
    for uid in window.requests:
        iv = prefill.get(uid)
        if iv is not None:
            start, end = iv.ts, iv.ts + iv.dur
        elif uid in queue:
            start, end = queue[uid].ts + queue[uid].dur, window.t1
        else:
            times.append(0.0)
            continue
        times.append(max(0.0, min(end, window.t1) - start))
    return float(np.percentile(times, 90)) * 1e3 if times else None


def data_wait_ms(rec, t_open: float, t_close: float):
    """Mean ``trainer.data`` span (the next batch and its bucket pin) over
    the steps that began it in the window."""
    return mean_ms([sp.dur for sp in rec.spans if sp.name == "trainer.data"
                    and t_open <= sp.ts <= t_close])


def longest(rec, name: str, t_open: float, t_close: float) -> str:
    """The longest ``name`` span in the window and its children's times."""
    top = [sp for sp in rec.spans
           if sp.name == name and t_open <= sp.ts <= t_close]
    if not top:
        return f"no {name} span in the window"
    sp = max(top, key=lambda s: s.dur)
    kids = ", ".join(f"{k.name} {k.dur * 1e3:.3f} ms"
                     for k in rec.spans if k.parent is sp)
    return (f"longest {name} {sp.dur * 1e3:.3f} ms at "
            f"{sp.ts - t_open:.3f} s: {kids or 'no children'}")


def kv_summary(at_open: dict, at_close: dict, page_size: int) -> str:
    """Mean pool pages held per decode step, the fill of those pages and
    the preemptions, from the ``engine.*`` counters' change over the
    window."""
    def delta(k):
        return at_close.get(k, 0.0) - at_open.get(k, 0.0)
    steps, pages = delta("engine.decode_steps"), delta("engine.kv.pages_held")
    if steps <= 0:
        return "no decode step in the window"
    fill = delta("engine.kv.tokens_held") / (pages * page_size) \
        if pages else 0.0
    return (f"KV pool: {pages / steps:.1f} pages held per decode step, "
            f"{100 * fill:.1f}% filled; "
            f"{delta('engine.preemptions'):.0f} preemptions")
