"""The open-loop runner against the program's PagedEngine at smoke size,
and the serving cell run whole on the CPU: correct when sound, not
correct when a served token is altered where it is produced."""
import time

import pytest

from chipbench import serve_cell, serving, traffic
from chipbench.tests import tree


def test_serving_records_every_due_request():
    import jax
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve.engine import PagedEngine
    cfg = get_config("granite-8b", smoke=True)
    model = build_model(cfg, mode="reference")
    engine = PagedEngine(model, model.init(jax.random.PRNGKey(0)),
                         batch_slots=4, page_size=16, max_pages_per_seq=16,
                         n_pages=49, chunk_tokens=32)
    serve_cell.warm(engine, cfg.vocab_size, 16, 32, 256)
    mix = dict(tree.TINY_CHAT, rate_per_s=4.0)
    arrivals = traffic.open_loop(mix, 3.0, 9, cfg.vocab_size)
    opened = []
    w = serving.run(engine, arrivals, 3.0,
                    on_open=lambda: opened.append(time.perf_counter()))
    assert set(w.served) == {a.uid for a in arrivals}
    assert set(w.requests) == {a.uid for a in arrivals if a.due_s >= 0}
    # the lead's requests were served before the window opened, there
    assert len(opened) == 1 and w.t0 <= opened[0] <= w.t0 + 0.5
    lead = [r for u, r in w.served.items() if u not in w.requests]
    assert lead and all(r.token_times and r.token_times[0] < w.t0
                        for r in lead)
    assert all(s.t0 >= w.t0 for s in w.steps)
    early = [r for r in w.requests.values() if r.due < w.t1 - 0.5]
    assert len(early) >= len(w.requests) // 2
    for r in early:
        # submitted once due, and at this load served its first token
        assert r.submitted >= r.due and r.token_times
    done = [r for r in w.requests.values() if r.done]
    assert done and all(len(r.token_times) == r.max_new for r in done)
    m = serve_cell.e2e_metrics(w)
    assert m["ttft_p90_ms"] > 0 and m["itl_p95_ms"] >= 0
    in_window = [t for r in w.served.values() for t in r.token_times
                 if t >= w.t0]
    assert m["output_tokens_per_s"] * w.seconds == pytest.approx(
        len(in_window))
    # the launches the window's steps ran account for its every token
    decoded = sum(len(s.decode_ctx) for s in w.steps)
    firsts = sum(1 for r in w.served.values()
                 if r.token_times and r.token_times[0] >= w.t0)
    assert decoded + firsts == len(in_window)
    prompt = sum(n for s in w.steps for _, n in s.chunks)
    assert prompt >= sum(r.prompt_len for r in done)


def test_serve_cell_correct_on_cpu(tmp_path):
    root = tree.build(str(tmp_path))
    rc, res, err = tree.run_cell(root, "tiny-lm.chat")
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tokens_per_s", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check served_logit_gap")


ALTER_TOKEN = """
from repro.serve import engine as E
_decode = E.PagedEngine._decode_one
def altered(self, active, mp):
    _decode(self, active, mp)
    for s in active:
        rec = self.slots[s]
        rec.generated[-1] = (rec.generated[-1] + 1) % self.model.cfg.vocab_size
        rec.next_token = rec.generated[-1]
E.PagedEngine._decode_one = altered
"""


def test_serve_cell_altered_token_is_not_correct(tmp_path):
    root = tree.build(str(tmp_path))
    rc, res, err = tree.run_cell(root, "tiny-lm.chat", prelude=ALTER_TOKEN)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
