"""A model family and a configuration added as files alone: a tree whose
``families/`` gains ``dense_copy.py`` (the dense family's text) and whose
configurations name it, with no ``rope_theta`` at their top level (the
family reads it, the harness does not), runs a serving and a training
cell through ``run.main`` on the CPU, correct, built by that family. The
same plain (``--trace 0``) runs open no ``obs`` capture."""
import json
import os

import pytest

from chipbench.tests import tree

PRELUDE = """
from chipbench import spec as _spec
from repro import obs as _obs
_family, _capture = _spec.family, _obs.capture
def family(root, name):
    mod = _family(root, name)
    print("family", name, mod.__file__, file=sys.stderr)
    return mod
def capture(**kw):
    print("obs capture opened", file=sys.stderr)
    return _capture(**kw)
_spec.family, _obs.capture = family, capture
"""
CELLS = {"copy-lm.chat": ("copy-lm", tree.TINY_LM, "tiny-chat",
                          "tiny-lm.chat"),
         "copy-cpm.train": ("copy-cpm", tree.TINY_CPM, "tiny-pack",
                            "tiny-cpm.train")}
_RUNS: dict = {}


def _tree(root: str) -> str:
    tree.build(root)
    with open(os.path.join(tree.REPO, "chipbench", "families",
                           "dense.py")) as fh:
        tree.add_files(root, "families", {"dense_copy.py": fh.read()})
    b = os.path.join(root, "chipbench")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    for cell, (name, base, mix, like) in CELLS.items():
        cfg = {k: v for k, v in base.items() if k != "rope_theta"}
        cfg.update(name=name, family="dense_copy")
        with open(os.path.join(b, "configs", f"{name}.json"), "w") as fh:
            json.dump(cfg, fh)
        with open(os.path.join(b, "cells", f"{like}.json")) as fh:
            limits = fh.read()
        with open(os.path.join(b, "cells", f"{cell}.json"), "w") as fh:
            fh.write(limits)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"chipbench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"]:
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    with open(path, "w") as fh:
        json.dump(bench, fh)
    return root


def _run(tmp_path_factory, workload: str):
    if workload not in _RUNS:
        root = _tree(str(tmp_path_factory.mktemp("family")))
        _RUNS[workload] = tree.run_cell(root, workload, prelude=PRELUDE)
    return _RUNS[workload]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_family_added_as_files_runs_correct(tmp_path_factory, workload):
    rc, res, err = _run(tmp_path_factory, workload)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    loaded = [line.split() for line in err.splitlines()
              if line.startswith("family ")]
    assert loaded and {n for _, n, _ in loaded} == {"dense_copy"}
    assert all(f.endswith(os.path.join("chipbench", "families",
                                       "dense_copy.py"))
               for _, _, f in loaded)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_plain_run_opens_no_capture(tmp_path_factory, workload):
    rc, res, err = _run(tmp_path_factory, workload)
    assert rc == 0 and res is not None, err[-3000:]
    assert "obs capture opened" not in err
    assert "breakdown" not in res
    assert "setup_s" in res["metrics"]
