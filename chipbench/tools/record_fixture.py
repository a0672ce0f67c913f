"""Record the two small traces the trace-reduction tests read, on the
chip, through the harness's own tracer: granite-8b at one layer serving a
few requests (paged attention and GEMM kernels), and minicpm-2b at one
layer taking one training step (the backward kernels). Writes gzipped
``.xplane.pb`` files into ``--out`` (default .chipbench_out/fixture/), to
be copied into chipbench/tests/data/.

    python3 chipbench/tools/record_fixture.py
"""
from __future__ import annotations

import dataclasses
import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
OUT = os.path.join(ROOT, ".chipbench_out", "fixture")


def save(tmp, name):
    from chipbench import trace_reduce
    src = trace_reduce.find_file(tmp)
    with open(src, "rb") as fh, gzip.open(os.path.join(OUT, name),
                                          "wb") as out:
        out.write(fh.read())
    shutil.rmtree(tmp)
    print(name, os.path.getsize(os.path.join(OUT, name)), flush=True)


def main():
    import argparse
    global OUT
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=OUT)
    OUT = ap.parse_args().out
    os.makedirs(OUT, exist_ok=True)
    import numpy as np
    from chipbench import model_spec, serving, system, traffic, weights
    from chipbench.run import Tracer
    from repro.serve.engine import PagedEngine, Request
    tmp = os.path.join(ROOT, ".chipbench_out", "fixture")

    spec = model_spec.load(os.path.join(ROOT, "chipbench/configs/"
                                              "granite-8b.json"))
    spec = dataclasses.replace(spec, layers=1)
    model = system.build(spec, "pallas_tpu")
    params = system.program_params(spec, weights.make(spec, 1), model)
    mix = traffic.load(os.path.join(ROOT, "chipbench/traffic/chat.json"))
    eng = PagedEngine(model, params,
                      **serving.engine_kwargs(spec.raw["serve"], mix))
    rng = np.random.default_rng(0)
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
    save(tmp, "serve.xplane.pb.gz")
    del eng, params, model

    from repro.optim import AdamWConfig, constant_schedule
    from repro.train import train_loop
    spec = model_spec.load(os.path.join(ROOT, "chipbench/configs/"
                                              "minicpm-2b.json"))
    spec = dataclasses.replace(spec, layers=1)
    model = system.build(spec, "pallas_tpu")
    params = system.program_params(spec, weights.make(spec, 1), model)
    model.init = lambda rng: params
    tmix = dict(traffic.load(os.path.join(
        ROOT, "chipbench/traffic/pretrain-4k.json")), seq_len=1024)
    tracer = Tracer(tmp)

    class Steps:                    # trace the third step alone
        def __gt__(self, step):
            if step == 2:
                tracer.start()
            if step == 3:
                tracer.stop()
            return step < 3

    train_loop(model, traffic.PackedDocs(tmix, 1, 0, spec.vocab), Steps(),
               AdamWConfig(schedule=constant_schedule(1e-3)), log_every=0,
               log=lambda *a, **k: None)
    save(tmp, "train.xplane.pb.gz")


if __name__ == "__main__":
    main()
