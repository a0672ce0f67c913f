"""The dense family gives exactly what the harness gave before model
families were files of their own: the same weights bit for bit, the same
work counts, and the same correctness readings of the tiny cells at a
fixed seed, each pinned to the value the earlier harness printed on the
CPU (readings that run no timed window, so they repeat exactly)."""
import dataclasses
import hashlib
import os

import numpy as np
import pytest

from chipbench import flops, model_spec, serve_cell, train_cell, traffic
from chipbench import weights
from chipbench.model_spec import family
from chipbench.correctness import train as check
from chipbench.tests import tree

SEED = 2 ** 33 + 11


def _digest(leaves: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(leaves):
        h.update(k.encode())
        h.update(np.asarray(leaves[k]).tobytes())
    return h.hexdigest()


def _config(name):
    return model_spec.load(os.path.join(tree.REPO, "chipbench", "configs",
                                        f"{name}.json"))


WEIGHTS = {
    "tiny-lm": "bbb6a7e0193dccc61f1aba189503f1f74550c4f7cd170512c8bc62f78f6a"
               "9dc9",
    "tiny-cpm": "d35815408d5ce97fcfad65b1ffeb41488165d844c1f3bd60c0f6a6e9f77d"
                "0ce5",
}
# every leaf but the embedding and the head, at one layer of the real
# widths (a stacked leaf's layer i is drawn from the key folded with i, so
# layer 0 at one layer is layer 0 at any depth)
LAYER0 = {
    "granite-8b": "643262ae5fc6cbe30f710d8c029a3009b8e22c8648f88e51aee86fc9"
                  "56ad87a0",
    "minicpm-2b": "b53a0fc291711da21ae5281a363dbcadfd984cb249f42327bb81d2ef"
                  "92a38461",
}
LEAVES = ["embed", "final_norm", "ln1", "ln2", "w_down", "w_gate", "w_up",
          "wo", "wqk", "wv"]


@pytest.mark.parametrize("cfg", [tree.TINY_LM, tree.TINY_CPM],
                         ids=["tiny-lm", "tiny-cpm"])
def test_tiny_weights_bit_for_bit(cfg):
    ms = model_spec.from_dict(cfg)
    assert ms.family == "dense"
    assert sorted(weights.shapes(ms)) == sorted(
        LEAVES + ([] if ms.tied else ["lm_head"]))
    assert _digest(weights.make(ms, SEED)) == WEIGHTS[cfg["name"]]


def test_real_configs_leaves_and_layer0_bit_for_bit():
    g, m = _config("granite-8b"), _config("minicpm-2b")
    assert weights.shapes(g)["wqk"] == ((16, 4096, 5120), "matrix")
    assert weights.shapes(g)["lm_head"] == ((4096, 49152), "matrix")
    assert weights.shapes(m)["w_down"] == ((6, 5760, 2304), "matrix")
    assert weights.shapes(m)["embed"] == ((122753, 2304), "embed")
    assert sorted(weights.shapes(m)) == LEAVES
    for ms in (g, m):
        w = weights.make(dataclasses.replace(ms, layers=1), SEED)
        assert _digest({k: v for k, v in w.items()
                        if k not in ("embed", "lm_head")}) == LAYER0[ms.name]
        del w


def test_work_counts_unchanged():
    """matmul weights a layer, head, attention at 1000 keys, training
    FLOPs a token at 4096, one engine step with two decode rows and two
    chunks, one layer's paged call over a decode row and a chunk."""
    want = {"granite-8b": [218103808, 201326592, 262144000.0,
                           23756931072.0, 5769857073152.0,
                           (10743250944.0, 15106048.0)],
            "minicpm-2b": [61046784, 282822912, 55296000.0, 4234443264.0,
                           616935684096.0, (6043078656.0, 19805184.0)]}
    for name, numbers in want.items():
        ms = _config(name)
        assert [family(ms).matmul_params(ms), flops.head_params(ms),
                flops.attn_flops(ms, 1000),
                flops.train_flops_per_token(ms, 4096),
                flops.serve_step_flops(ms, [100, 2000],
                                       [(0, 512), (512, 300)]),
                flops.paged_attention_call(
                    ms, [(1, 99, 1), (512, 1024, 512)])] == numbers


def test_tiny_serving_readings_unchanged():
    """Eight requests served to the end by the engine (no clock), every
    served token's gap and the fp8 control's, against the reference."""
    from repro.serve.engine import Request
    ms = model_spec.from_dict(tree.TINY_LM)
    engine = serve_cell.build(ms, tree.TINY_CHAT, SEED, "reference")
    arrivals = traffic.open_loop(tree.TINY_CHAT, 2.0, SEED, ms.vocab)[:8]
    for a in arrivals:
        engine.submit(Request(a.uid, a.prompt, a.max_new_tokens,
                              temperature=0.0))
    while engine.step():
        pass
    finished = {a.uid: engine.results[a.uid] for a in arrivals}
    plens = {a.uid: len(a.prompt) for a in arrivals}
    uids, served, control = serve_cell.compare(ms, SEED, finished, plens,
                                               "fp8")
    assert uids == [4, 3, 7, 2, 0, 6, 1, 5] and len(served) == 69
    assert repr(float(served.max())) == "0.008469581604003906"
    assert repr(float(control.max())) == "0.13915276527404785"
    assert hashlib.sha256(np.asarray(served).tobytes()).hexdigest() == (
        "a473a2565618026fd30675db77d5c8dc311061ead2fee20d2bdefe45c80a3ce8")
    assert hashlib.sha256(np.asarray(control).tobytes()).hexdigest() == (
        "f326ff30a1215dfe70feaf5c7cc7b734ff05031d201f5894aec33fbd221ea12b")


class _Warm:
    def __gt__(self, step):
        return step < train_cell.WARM


def test_tiny_training_readings_unchanged():
    ms = model_spec.from_dict(tree.TINY_CPM)
    _, prog, feed = train_cell.program(ms, tree.TINY_PACK, SEED,
                                       "reference", _Warm(),
                                       lambda *a, **k: None)
    ref = train_cell.reference(ms, SEED, feed)
    assert [repr(x) for x in ref["losses"]] == [
        "6.2676682472229", "6.284109115600586", "6.2549357414245605"]
    got = check.compare(prog, ref)
    assert {k: repr(v) if isinstance(v, float) else v
            for k, v in got.items()} == {
        "loss_gap": "0.00010776519775390625",
        "grad_norm_gap": "0.0018828398806053658", "worst_grad_leaf": "wqk",
        "grad_rel_l2": "0.010793591124309502", "worst_grad_dir_leaf": "wqk",
        "update_norm_gap": "0.0005379908923568064",
        "worst_update_leaf": "ln2", "left_out": []}
