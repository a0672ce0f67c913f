"""Readings the correctness limits are set from, on the chip, at the
cell's own size, in one process: for each seed, the program's numbers
against the reference and, on the first ``--control-seeds`` seeds, the
control's (the reference in fp8, in the program's place) and, for a
training cell, a planted fault's (the step sees half of its batch). The
benchmark's own runs never run these. One JSON line per seed.

    python3 chipbench/tools/calibrate.py --workload granite-8b.chat \
        --seeds 11 12 13 --control-seeds 3 --seconds 20
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def serve(ms, mix, seed, seconds, control):
    import numpy as np
    from chipbench import serve_cell, serving, traffic
    engine = serve_cell.build(ms, mix, seed, MODE)
    arrivals = traffic.open_loop(mix, seconds, seed, ms.vocab)
    window = serving.run(engine, arrivals, seconds)
    finished = serve_cell.drain(engine, window)
    del engine
    gc.collect()
    plens = {a.uid: len(a.prompt) for a in arrivals}
    uids, served, ctrl = serve_cell.compare(
        ms, seed, finished, plens, "fp8" if control else None)
    out = {"requests": len(uids), "tokens": int(len(served)),
           "served_logit_gap": float(served.max()),
           "served_gap_p99": float(np.percentile(served, 99)),
           "served_tokens_off_best": int((served > 0).sum())}
    if control:
        out.update(control_logit_gap=float(ctrl.max()),
                   control_gap_p50=float(np.percentile(ctrl, 50)),
                   control_tokens_off_best=int((ctrl > 0).sum()))
    return out


HALF = "half of the batch"
MODE = "pallas_tpu"


def _half_batch(make):
    def broken(model, opt_cfg, **kw):
        step = make(model, opt_cfg, **kw)

        def half(state, batch):
            return step(state, {k: v[: v.shape[0] // 2]
                                for k, v in batch.items()})
        return half
    return broken


def train(ms, mix, seed, control):
    from chipbench import train_cell
    from chipbench.correctness import train as check
    from repro.train import trainer

    class Warm:
        def __gt__(self, step):
            return step < train_cell.WARM

    _, prog, feed = train_cell.program(ms, mix, seed, MODE, Warm(),
                                       lambda *a, **k: None)
    ref = train_cell.reference(ms, seed, feed)
    out = {"program": check.compare(prog, ref), "ref_losses": ref["losses"],
           "losses": prog["losses"]}
    if control:
        ctrl = train_cell.reference(ms, seed, feed, "fp8")
        out["control"] = check.compare(ctrl, ref)
        make = trainer.make_train_step
        trainer.make_train_step = _half_batch(make)
        try:
            _, half, _ = train_cell.program(ms, mix, seed, MODE,
                                            Warm(), lambda *a, **k: None)
        finally:
            trainer.make_train_step = make
        out[HALF] = check.compare(half, ref)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20)
    args = ap.parse_args()
    from chipbench.run import compile_cache
    compile_cache(ROOT)
    from chipbench import model_spec, spec, traffic
    cell = spec.cell(ROOT, args.workload)
    ms = model_spec.load(cell.config_file)
    mix = traffic.load(cell.traffic_file)
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        control = i < args.control_seeds
        if mix["kind"] == "train":
            out = train(ms, mix, seed, control)
        else:
            out = serve(ms, mix, seed, args.seconds, control)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t,
                          **out}), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
