"""BENCHMARK.json and the files it names, found by name: a cell's
configuration is ``configs/<config>.json`` (via the entry's ``file``), its
model family ``families/<family>.py`` (the configuration's ``family``
key), its traffic ``traffic/<traffic>.json``, its limits
``cells/<workload>.json`` and each per-layer metric
``metrics/<metric>.py``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Cell:
    name: str
    config: dict            # the BENCHMARK.json entry
    config_file: str
    traffic: str
    traffic_file: str
    chips: int
    end_to_end: list        # metric entries this cell reports
    per_layer: list


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def cell(root: str, name: str) -> Cell:
    bench = load(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"] if _applies(m, name, names)]
    bench_dir = os.path.join(root, os.path.basename(HERE))
    return Cell(name, cfg, os.path.join(root, cfg["file"]), w["traffic"],
                os.path.join(bench_dir, "traffic", f"{w['traffic']}.json"),
                int(w["chips"]), e2e, per)


_MODULES: dict = {}


def _module(root: str, kind: str, name: str):
    """``<root>/chipbench/<kind>/<name>.py``, loaded once."""
    path = os.path.join(root, os.path.basename(HERE), kind, f"{name}.py")
    if path not in _MODULES:
        mod_name = f"chipbench_{kind}_" + re.sub(r"\W", "_", name)
        mod_spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[mod_name] = mod
        mod_spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def metric_reader(root: str, name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return _module(root, "metrics", name).read


def family(root: str, name: str):
    """The module ``families/<name>.py``: everything the harness knows of
    one model architecture (``families/dense.py`` lists what it holds)."""
    return _module(root, "families", name)
