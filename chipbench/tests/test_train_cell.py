"""The training cell run whole on the CPU: correct when sound, not correct
when the step leaves its state unchanged or sees half of its batch."""
from chipbench.tests import tree


def test_train_cell_correct_on_cpu(tmp_path):
    root = tree.build(str(tmp_path))
    rc, res, err = tree.run_cell(root, "tiny-cpm.train")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0


UNCHANGED = """
import repro.train.trainer as T
_make = T.make_train_step
def broken(model, opt_cfg, **kw):
    kw["donate"] = False
    step = _make(model, opt_cfg, **kw)
    def same_state(state, batch):
        return state, step(state, batch)[1]
    return same_state
T.make_train_step = broken
"""

HALF_BATCH = """
import repro.train.trainer as T
_make = T.make_train_step
def broken(model, opt_cfg, **kw):
    step = _make(model, opt_cfg, **kw)
    def half(state, batch):
        return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
    return half
T.make_train_step = broken
"""


def _fails(tmp_path, prelude):
    root = tree.build(str(tmp_path))
    rc, res, err = tree.run_cell(root, "tiny-cpm.train", prelude=prelude)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    return {k: c["value"] / c["limit"] for k, c in res["checks"].items()}


def test_state_left_unchanged_is_not_correct(tmp_path):
    over = _fails(tmp_path, UNCHANGED)
    assert over["update_norm_gap"] > 10 and over["grad_norm_gap"] > 10


def test_half_the_batch_is_not_correct(tmp_path):
    over = _fails(tmp_path, HALF_BATCH)
    assert max(over.values()) > 1
