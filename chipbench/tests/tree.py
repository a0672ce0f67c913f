"""A benchmark tree of tiny cells for CPU tests: BENCHMARK.json, configs,
traffic mixes and limits written into a temporary directory, with ``src``,
``metrics/`` and ``families/`` linked to the repository's, so
``run.main(root=...)`` finds everything by name there. ``add_files`` lays a
real directory of links in place of one of those, with files of its own
beside them."""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_LM = {
    "name": "tiny-lm", "num_hidden_layers": 2, "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "tie_word_embeddings": False, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "dtype": "bfloat16",
    "serve": {"batch_slots": 4, "page_size": 16, "max_seq_tokens": 256,
              "kv_pool_pages": 48, "chunk_tokens": 32}}
TINY_CPM = {
    "name": "tiny-cpm", "num_hidden_layers": 2,
    "published_num_hidden_layers": 40, "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 500,
    "tie_word_embeddings": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "scale_emb": 12.0, "scale_depth": 1.4,
    "dim_model_base": 16, "dtype": "float32", "compute_dtype": "bfloat16",
    "train": {"micro_batch": 2, "learning_rate": 1e-3, "b1": 0.9,
              "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
              "clip_norm": 1.0}}
TINY_CHAT = {"kind": "serve_open_loop", "rate_per_s": 6.0,
             "lead_seconds": 1.0,
             "prompt_tokens": {"median": 40, "sigma": 0.6, "min": 8,
                               "max": 120},
             "output_tokens": {"median": 6, "sigma": 0.9, "min": 2,
                               "max": 16},
             "prefix_cache": False}
TINY_PACK = {"kind": "train", "seq_len": 64, "mean_doc_len": 16,
             "noise": 0.2}
# limits of these tiny cells, set between readings on the CPU (seeds 5,
# 6 and the tests' own): the bf16 program against the float32 reference
# reads served gap <= 0.003, loss 6e-5-1.2e-4, gradient norm 0.0014-0.0024,
# gradient direction (each leaf against its own norm) 0.011-0.012, update
# 3e-4-6e-4; the fp8 control served gap 0.15, loss 9e-4-1.4e-3, gradient
# norm 0.004-0.010, direction 0.133-0.139, update 0.002-0.004; half of the
# batch loss 0.017-0.026, gradient norm 0.37-0.43, direction 0.99-1.16
LIMITS = {"tiny-lm.chat": {"served_logit_gap": 0.05},
          "tiny-cpm.train": {"loss_gap": 5e-4, "grad_norm_gap": 0.005,
                             "grad_rel_l2": 0.03,
                             "update_norm_gap": 0.0015}}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


def build(root: str) -> str:
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {"tiny-lm.chat": ("tiny-lm", "tiny-chat"),
             "tiny-cpm.train": ("tiny-cpm", "tiny-pack")}
    bench["configs"] = [{"name": n, "source": "test",
                         "file": f"chipbench/configs/{n}.json",
                         "reduced": [], "why": "test"}
                        for n in ("tiny-lm", "tiny-cpm")]
    bench["workloads"] = [{"name": w, "config": c, "traffic": t,
                           "chips": 1, "why": "test"}
                          for w, (c, t) in cells.items()]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = (["tiny-cpm.train"] if m["name"].startswith(
                "train") else ["tiny-lm.chat"])
    for m in bench["per_layer"]:
        m["workloads"] = (["tiny-cpm.train"] if m["moves"].startswith(
            "train") else ["tiny-lm.chat"])
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    b = os.path.join(root, "chipbench")
    _dump(os.path.join(b, "configs", "tiny-lm.json"), TINY_LM)
    _dump(os.path.join(b, "configs", "tiny-cpm.json"), TINY_CPM)
    _dump(os.path.join(b, "traffic", "tiny-chat.json"), TINY_CHAT)
    _dump(os.path.join(b, "traffic", "tiny-pack.json"), TINY_PACK)
    for w, lim in LIMITS.items():
        _dump(os.path.join(b, "cells", f"{w}.json"),
              {"limits": {k: {"limit": v} for k, v in lim.items()}})
    for kind in ("metrics", "families"):
        os.symlink(os.path.join(REPO, "chipbench", kind),
                   os.path.join(b, kind))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    return root


def add_files(root: str, kind: str, files: dict) -> None:
    """Make ``chipbench/<kind>/`` of the tree a directory holding links to
    the repository's files and ``files`` ({name: text}) of its own."""
    d = os.path.join(root, "chipbench", kind)
    os.unlink(d)
    os.makedirs(d)
    src = os.path.join(REPO, "chipbench", kind)
    for name in os.listdir(src):
        if name.endswith(".py"):
            os.symlink(os.path.join(src, name), os.path.join(d, name))
    for name, text in files.items():
        with open(os.path.join(d, name), "w") as fh:
            fh.write(text)


RUNNER = """
import json, sys
sys.path[:0] = [{repo!r}, {src!r}]
{prelude}
from chipbench import run
sys.exit(run.main({argv!r}, root={root!r}, require_chip=False,
                  mode="reference"))
"""


def run_cell(root: str, workload: str, *, seed: int = 2 ** 33 + 7,
             seconds: float = 2.0, prelude: str = "", timeout=300,
             trace: int = 0):
    """Run one tiny cell in a fresh CPU process; returns (rc, the parsed
    last stdout line or None, stderr)."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    code = RUNNER.format(repo=REPO, src=os.path.join(REPO, "src"),
                         prelude=prelude, argv=argv, root=root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                       capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines and lines[-1].startswith(
        "{") else None
    return p.returncode, result, p.stderr
