"""Arithmetic the per-layer metric readers of ``metrics/`` share."""
from __future__ import annotations

import numpy as np

from . import flops


def idle_share(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def percentile_ms(values, q):
    return float(np.percentile(values, q)) * 1e3 if len(values) else None


def paged_attention_roofline(run):
    """Least time of every paged-attention call in the traced steps (one
    per layer for each step's decode launch and for each prefill chunk)
    over the device time of the ``flash_decode_paged`` calls."""
    calls = run.trace.kernel_calls("flash_decode_paged") if run.trace \
        else []
    w = run.window
    steps = w.steps[w.traced_steps:] if w.traced_steps >= 0 else []
    if not calls or not steps:
        return None
    least = 0.0
    for st in steps:
        launches = [[(1, c - 1, 1) for c in st.decode_ctx]] if st.decode_ctx \
            else []
        launches += [[(n, start, n)] for start, n in st.chunks]
        for rows in launches:
            least += flops.paged_attention_launch(run.spec, rows, run.peak)
    return 100.0 * least / run.trace.seconds(calls)
