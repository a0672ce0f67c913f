"""The comparisons that decide ``correct``: what the timed path produced
against the plain reference (``reference.py``), each number against the
limit of its cell (``cells/<workload>.json``)."""
from __future__ import annotations

import json
import os

BENCH_DIR = os.path.basename(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def limits(root: str, workload: str) -> dict:
    with open(os.path.join(root, BENCH_DIR, "cells",
                           f"{workload}.json")) as fh:
        return {k: v["limit"] for k, v in json.load(fh)["limits"].items()}


def judge(readings: dict, lim: dict) -> tuple[bool, dict]:
    """Every reading at or under its limit; a missing or non-finite
    reading fails."""
    import math
    checks, ok = {}, True
    for name, limit in lim.items():
        v = readings.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok &= good
        checks[name] = {"value": v, "limit": limit}
    return ok, checks
