"""The generator: the same seed gives the same schedule, every seed the
same sizes, and the lengths sit at the stated quantiles."""
import os

import numpy as np

from chipbench import traffic
from chipbench.tests.tree import REPO

CHAT = traffic.load(os.path.join(REPO, "chipbench", "traffic", "chat.json"))


def _key(arr):
    return [(a.uid, a.due_s, a.max_new_tokens, a.prompt.tobytes())
            for a in arr]


def test_same_seed_same_schedule():
    big = 2 ** 33 + 12345
    assert _key(traffic.open_loop(CHAT, 51, big, 49152)) == _key(
        traffic.open_loop(CHAT, 51, big, 49152))
    assert _key(traffic.open_loop(CHAT, 51, 1, 49152)) != _key(
        traffic.open_loop(CHAT, 51, 2, 49152))      # the tokens differ


def test_every_seed_same_schedule_other_tokens():
    a = traffic.open_loop(CHAT, 51, 11, 49152)
    b = traffic.open_loop(CHAT, 51, 12, 49152)
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == [
        (x.due_s, len(x.prompt), x.max_new_tokens) for x in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    lead = CHAT["lead_seconds"]
    assert round(CHAT["rate_per_s"] * (lead + 51)) - len(a) in (0, 1)
    assert -lead <= min(x.due_s for x in a) and max(x.due_s for x in a) < 51
    due = sum(x.due_s >= 0 for x in a)
    assert abs(due - CHAT["rate_per_s"] * 51) <= 2
    # the order is drawn, not sorted (at a rate and length where rounding
    # leaves no request past the close)
    other = dict(CHAT, order_seed=CHAT.get("order_seed", 0) + 1)
    a = traffic.open_loop(CHAT, 40, 11, 49152, rate=0.5)
    c = traffic.open_loop(other, 40, 11, 49152, rate=0.5)
    assert len(a) == len(c) == 0.5 * (lead + 40)
    assert [len(x.prompt) for x in c] != [len(x.prompt) for x in a]
    assert sorted(len(x.prompt) for x in c) == sorted(len(x.prompt)
                                                      for x in a)


def test_length_quantiles_are_the_stated_ones():
    arr = traffic.open_loop(CHAT, 400, 3, 49152)
    p = np.array([len(a.prompt) for a in arr])
    o = np.array([a.max_new_tokens for a in arr])
    pt, ot = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert abs(np.median(p) - pt["median"]) <= 2
    assert abs(np.median(o) - ot["median"]) <= 1
    assert p.min() >= pt["min"] and p.max() <= pt["max"]
    assert o.min() >= ot["min"] and o.max() <= ot["max"]
    # the 84th percentile of a log-normal is median * e^sigma
    assert abs(np.percentile(p, 84.13) / (pt["median"]
                                          * np.exp(pt["sigma"])) - 1) < 0.03
    assert abs(np.percentile(o, 84.13) / (ot["median"]
                                          * np.exp(ot["sigma"])) - 1) < 0.03
    gaps = np.diff(sorted(a.due_s for a in arr))
    assert abs(gaps.mean() * CHAT["rate_per_s"] - 1) < 0.02


def test_packed_rows_depend_on_seed_and_step_alone():
    mix = traffic.load(os.path.join(REPO, "chipbench", "traffic",
                                    "pretrain-4k.json"))
    a = traffic.PackedDocs(mix, 2, 2 ** 33, 122753)
    b = traffic.PackedDocs(mix, 2, 2 ** 33, 122753)
    x, y = a.batch_at(3), b.batch_at(3)
    assert all(np.array_equal(x[k], y[k]) for k in x)
    assert x["inputs"].shape == (2, 4096)
    assert np.array_equal(x["inputs"][:, 1:], x["targets"][:, :-1])
    assert not np.array_equal(a.batch_at(4)["inputs"], x["inputs"])
    # documents break the loss about once per mean_doc_len tokens
    assert abs(1 - x["loss_mask"].mean() - 1 / mix["mean_doc_len"]) < 0.002
