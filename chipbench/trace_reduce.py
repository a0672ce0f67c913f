"""A profiler trace (``.xplane.pb``) reduced to what the metrics read:
device busy time and idle share over the traced window, every device
operation with its HLO text (which carries its shapes), the program's
spans, and the longest idle gaps labelled by what the host was doing.

The traced window is the host event named ``WINDOW`` (a
``jax.profiler.TraceAnnotation`` the harness opens around the window);
device and host events share the trace's clock. Device time is the union
of the intervals of the ``XLA Ops`` line of each ``/device:`` plane,
averaged over the devices that ran anything.

Under ``obs.capture(annotate=True)`` (``run.py --trace 1``) the program's
spans (``engine.*``, ``trainer.*``) are host events of the trace too,
named with ``PREFIX``. Each idle gap is labelled "harness annotation /
program span / runtime event", each the innermost one around the gap's
middle, and the device's idle time is summed by the innermost program
span (``idle_by_span``).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW = "chipbench.window"
OWN_PREFIX = "chipbench."
PREFIX = "repro."           # the program's spans (repro.obs)
OUTSIDE = "outside the program spans"
CONTAINERS = ("while", "conditional", "call")   # their time is their body's
_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|f8e4m3fn|f8e5m2|"
                    r"bf16|f16|f32|f64)\[([\d,]*)\]")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
            "s16": 2, "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
            "f32": 4, "s64": 8, "u64": 8, "f64": 8}


def op_name(text: str) -> str:
    """The HLO instruction's name without its number: '_gemm_pallas'."""
    m = _NAME.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def _shapes(s: str) -> list:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(s)]


def call_shapes(text: str) -> tuple[list, list]:
    """(outputs, operands) of a custom call as [(dtype, shape)]."""
    head, _, rest = text.partition(" custom-call(")
    operands = rest.split("), custom_call_target=", 1)[0]
    return _shapes(head.split("=", 1)[-1]), _shapes(operands)


def nbytes(shapes: list) -> int:
    total = 0
    for dt, shape in shapes:
        n = ITEMSIZE[dt]
        for d in shape:
            n *= d
        total += n
    return total


@dataclasses.dataclass
class Op:
    text: str
    start_ns: float
    dur_ns: float
    module: str

    @property
    def name(self) -> str:
        return op_name(self.text)

    @property
    def is_kernel(self) -> bool:
        return 'custom_call_target="tpu_custom_call"' in self.text


@dataclasses.dataclass
class Trace:
    window_s: float
    busy_s: float
    devices: int
    ops: list                   # Op inside the window (all devices)
    gaps: list                  # [label, seconds], longest first
    spans: list = dataclasses.field(default_factory=list)
    # (start_ns, end_ns, name, rid) of each program span in the window
    idle_by_span: dict = dataclasses.field(default_factory=dict)
    # innermost program span -> idle seconds, most first

    def kernel_calls(self, name: str) -> list:
        return [op for op in self.ops if op.is_kernel and op.name == name]

    def seconds(self, ops) -> float:
        return sum(op.dur_ns for op in ops) * 1e-9 / self.devices

    def top_ops(self, n: int = 10) -> list:
        """Device time per operation (by name, output and program)."""
        acc = collections.Counter()
        for op in self.ops:
            if op.name in CONTAINERS:
                continue
            out = op.text.split("=", 1)[-1].split("{", 1)[0].strip()
            key = f"{op.name} {out[:60]} in {op.module[:40]}"
            acc[key] += op.dur_ns * 1e-9 / self.devices
        return [[k, v] for k, v in acc.most_common(n)]


def find_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"{trace_dir}: expected one .xplane.pb, "
                                f"found {files}")
    return files[0]


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _inner(events, t: float):
    """Name of the innermost event (latest start) holding ``t``."""
    best = None
    for ev in events:
        if ev[0] <= t <= ev[1] and (best is None or ev[0] >= best[0]):
            best = ev
    return best[2] if best else None


def _label(mid: float, own: list, program: list, runtime: list) -> str:
    return " / ".join((
        _inner(own, mid) or "outside the harness's annotations",
        _inner(program, mid) or OUTSIDE,
        _inner(runtime, mid) or "host Python"))


def _idle_by_span(gaps: list, program: list) -> dict:
    idle: dict = {}
    for s, e in gaps:
        inside = [sp for sp in program if sp[1] > s and sp[0] < e]
        cuts = sorted({s, e} | {t for sp in inside for t in sp[:2]
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            name = _inner(inside, (a + b) / 2) or OUTSIDE
            idle[name] = idle.get(name, 0.0) + (b - a) * 1e-9
    return dict(sorted(idle.items(), key=lambda kv: -kv[1]))


def load(path: str):
    """ProfileData of an ``.xplane.pb`` file, gzipped or not."""
    import gzip
    import jax
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return jax.profiler.ProfileData.from_serialized_xspace(fh.read())
    return jax.profiler.ProfileData.from_file(path)


def reduce(path: str, n_gaps: int = 10) -> Trace:
    pd = load(path)
    window, own, program, runtime = None, [], [], []
    device_lines = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                device_lines.append((lines["XLA Ops"],
                                     lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name)
                    if ev.name == WINDOW:
                        window = span[:2]
                    elif ev.name.startswith(OWN_PREFIX):
                        own.append(span)
                    elif ev.name.startswith(PREFIX):
                        program.append((*span[:2], ev.name[len(PREFIX):],
                                        dict(ev.stats).get("rid")))
                    elif not ev.name.startswith("$"):
                        runtime.append(span)
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    w0, w1 = window
    ops, busy, devices, intervals = [], 0.0, 0, []
    for op_line, mod_line in device_lines:
        mods = ([(e.start_ns, e.start_ns + e.duration_ns, e.name)
                 for e in mod_line.events] if mod_line is not None else [])
        mods.sort()
        mi, ran = 0, []
        for ev in op_line.events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            while mi < len(mods) and mods[mi][1] < s:
                mi += 1
            module = mods[mi][2] if mi < len(mods) and mods[mi][0] <= s \
                else ""
            ops.append(Op(ev.name, s, e - s, module))
            ran.append((s, e))
        if ran:
            devices += 1
            merged = _union(ran)
            busy += sum(e - s for s, e in merged)
            intervals = merged if not intervals else intervals
    devices = max(devices, 1)
    gaps, prev = [], w0
    for s, e in intervals + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    program = sorted((sp for sp in program if sp[1] > w0 and sp[0] < w1),
                     key=lambda sp: sp[:2])
    idle = _idle_by_span(gaps, program)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [[_label((s + e) / 2, own, program, runtime), (e - s) * 1e-9]
                for s, e in gaps[:n_gaps]]
    return Trace((w1 - w0) * 1e-9, busy * 1e-9 / devices, devices, ops,
                 labelled, program, idle)
