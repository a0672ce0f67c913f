"""The control (the reference in fp8, in the program's place) comes out
not correct against each tiny cell's limits, while the program is
correct: the comparison can fail."""
from chipbench import correctness, model_spec, serve_cell, serving
from chipbench import train_cell, traffic
from chipbench.correctness import train as check
from chipbench.tests import tree

SEED = 2 ** 33 + 7


def test_serving_control_fails_program_passes():
    ms = model_spec.from_dict(tree.TINY_LM)
    engine = serve_cell.build(ms, tree.TINY_CHAT, SEED, "reference")
    arrivals = traffic.open_loop(tree.TINY_CHAT, 2.0, SEED, ms.vocab)
    serving.run(engine, arrivals, 2.0)
    finished = {u: r for u, r in engine.results.items() if u >= 0}
    plens = {a.uid: len(a.prompt) for a in arrivals}
    _, served, control = serve_cell.compare(ms, SEED, finished, plens, "fp8")
    limit = tree.LIMITS["tiny-lm.chat"]["served_logit_gap"]
    assert len(served) >= 50
    assert served.max() <= limit < control.max()


class _Warm:
    def __gt__(self, step):
        return step < train_cell.WARM


def test_training_control_fails_program_passes():
    ms = model_spec.from_dict(tree.TINY_CPM)
    _, prog, feed = train_cell.program(ms, tree.TINY_PACK, SEED,
                                       "reference", _Warm(),
                                       lambda *a, **k: None)
    ref = train_cell.reference(ms, SEED, feed)
    ctrl = train_cell.reference(ms, SEED, feed, "fp8")
    lim = tree.LIMITS["tiny-cpm.train"]
    ok, _ = correctness.judge(check.compare(prog, ref), lim)
    bad, checks = correctness.judge(check.compare(ctrl, ref), lim)
    assert ok and not bad, checks
