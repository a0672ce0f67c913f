"""The harness finds a cell's files by name, and refuses to run without a
chip or outside a checkout."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import spec
from chipbench.tests import tree


def test_cell_config_traffic_and_metric_found_by_name(tmp_path):
    root = tree.build(str(tmp_path))
    b = os.path.join(root, "chipbench")
    # a configuration, a traffic mix, a cell and a per-layer metric that
    # exist only in this tree
    with open(os.path.join(b, "configs", "tiny-lm.json")) as fh:
        cfg = json.load(fh)
    cfg["name"] = "other-lm"
    with open(os.path.join(b, "configs", "other-lm.json"), "w") as fh:
        json.dump(cfg, fh)
    with open(os.path.join(b, "traffic", "burst.json"), "w") as fh:
        json.dump(dict(tree.TINY_CHAT, rate_per_s=9.0), fh)
    os.unlink(os.path.join(b, "metrics"))
    os.makedirs(os.path.join(b, "metrics"))
    with open(os.path.join(b, "metrics", "steps_seen.py"), "w") as fh:
        fh.write("def read(run):\n    return float(len(run.window))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "other-lm", "source": "test",
                             "file": "chipbench/configs/other-lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other-lm.burst",
                               "config": "other-lm", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"] = [{"name": "steps_seen", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "engine", "moves": "itl_p95_ms"}]
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("other-lm.burst")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    cell = spec.cell(root, "other-lm.burst")
    assert cell.config_file == os.path.join(b, "configs", "other-lm.json")
    assert cell.traffic_file == os.path.join(b, "traffic", "burst.json")
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms",
                                                    "setup_s"]
    # no 'workloads' key: the metric goes to every cell reporting what
    # it moves
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    assert spec.metric_reader(root, "steps_seen")(
        type("R", (), {"window": [1, 2, 3]})()) == 3.0
    assert [m["name"] for m in spec.cell(root, "tiny-cpm.train")
            .per_layer] == []


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "granite-8b.chat",
         "--seed", "5", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(tree.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_only_the_benchmark_files_no_result(tmp_path):
    shutil.copy(os.path.join(tree.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tree.REPO, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
