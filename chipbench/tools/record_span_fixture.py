"""Record the trace the span tests read, on the chip, through the
harness's own tracer: granite-8b at one layer serving three requests with
512-token prefill chunks, under ``obs.capture(annotate=True)``, so that the
program's spans and named programs lie in it beside the device ops. Writes
``serve_spans.xplane.pb.gz`` into ``--out`` (default
.chipbench_out/fixture/), to be copied into chipbench/tests/data/.

    python3 chipbench/tools/record_span_fixture.py
"""
from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main():
    import argparse
    from chipbench.tools import record_fixture
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=record_fixture.OUT)
    record_fixture.OUT = ap.parse_args().out
    os.makedirs(record_fixture.OUT, exist_ok=True)
    import numpy as np
    from chipbench import model_spec, serving, system, traffic, weights
    from chipbench.run import Tracer
    from repro import obs
    from repro.serve.engine import PagedEngine, Request
    tmp = os.path.join(ROOT, ".chipbench_out", "span_fixture")

    spec = model_spec.load(os.path.join(ROOT, "chipbench/configs/"
                                              "granite-8b.json"))
    spec = dataclasses.replace(spec, layers=1)
    model = system.build(spec, "pallas_tpu")
    params = system.program_params(spec, weights.make(spec, 1), model)
    mix = traffic.load(os.path.join(ROOT, "chipbench/traffic/chat.json"))
    eng = PagedEngine(model, params,
                      **serving.engine_kwargs(spec.raw["serve"], mix))
    rng = np.random.default_rng(0)
    with obs.capture(annotate=True):
        for rnd in range(2):            # the first round compiles
            for uid, (p, n) in enumerate([(600, 4), (130, 6), (70, 5)]):
                eng.submit(Request(rnd * 10 + uid, rng.integers(
                    0, 1000, p).astype(np.int32), n, temperature=0.0))
            tracer = Tracer(tmp) if rnd else None
            if tracer:
                tracer.start()
            while eng.step():
                pass
            if tracer:
                tracer.stop()
    record_fixture.save(tmp, "serve_spans.xplane.pb.gz")


if __name__ == "__main__":
    main()
