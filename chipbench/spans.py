"""What the program's own names and spans add to a traced run.

The program names its jitted serving programs (``decode_step_paged``,
``prefill_paged_chunk``, ...), so a device trace's ``XLA Modules`` line
tells them apart: ``executions`` gives the device time of each execution
of one of them from the reduced trace alone.

A traced run (``run.py --trace 1``) runs its cell under
``obs.capture(annotate=True)``; ``trace_reduce`` places the program's
spans in the profiler trace beside the device ops. The readers below take
that capture's recorder (``Run.rec``): the engine's request phases
(``engine.request.*`` intervals), the trainer's ``trainer.data`` spans, the
longest step, and the KV pool from the counters.
"""
from __future__ import annotations

import numpy as np


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
