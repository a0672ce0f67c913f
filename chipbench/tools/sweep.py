"""Find the knee of a serving cell once: one engine, set up once, is
offered the cell's mix at each rate in turn, lowest first, without a
drain between rates. Each rate runs ``--lead`` seconds untimed, so that
its window opens on an engine already holding that rate's occupancy, then
a window of ``--seconds``, several request lifetimes long.

For each rate it prints the requests held (waiting or in a slot) and
those waiting for a slot over each third of the window, the output tokens
per second against those offered, and the time to first token. A rate is
sustained when the waiting queue does not grow: its mean over the last
third of the window is at most one request above the first third's. The
knee is the highest rate below the first one not sustained; the sweep
stops after two rates in a row that are not.

    python3 chipbench/tools/sweep.py --workload granite-8b.chat \
        --rates 0.3 0.45 0.6 --lead 60 --seconds 120 --seed 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

def thirds(samples, t0: float, seconds: float) -> list:
    """Mean of the sampled values over each third of the window."""
    out = []
    for k in range(3):
        lo, hi = t0 + k * seconds / 3, t0 + (k + 1) * seconds / 3
        vals = [v for t, v in samples if lo <= t < hi]
        out.append(sum(vals) / len(vals) if vals else float("nan"))
    return out


def sustained(waiting: list) -> bool:
    return waiting[2] <= waiting[0] + 1.0


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--lead", type=float, default=60)
    ap.add_argument("--seconds", type=float, default=120)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from chipbench.run import compile_cache
    compile_cache(ROOT)
    from chipbench import model_spec, serve_cell, serving, spec, traffic
    cell = spec.cell(ROOT, args.workload)
    ms = model_spec.load(cell.config_file)
    mix = traffic.load(cell.traffic_file)
    engine = serve_cell.build(ms, mix, args.seed, "pallas_tpu")
    knee, missed, misses = None, False, 0
    for i, rate in enumerate(sorted(args.rates)):
        arrivals = traffic.open_loop(mix, args.seconds, args.seed + i,
                                     ms.vocab, rate=rate, lead=args.lead)
        for a in arrivals:
            a.uid += 100000 * (i + 1)
        held, waiting = [], []

        class Probe:            # samples the engine after each step
            def __init__(self, eng):
                self.eng = eng

            def __getattr__(self, name):
                return getattr(self.eng, name)

            def step(self):
                out = self.eng.step()
                t, n = time.perf_counter(), len(self.eng.pending)
                waiting.append((t, n))
                held.append((t, n + len(self.eng.slots)))
                return out

        w = serving.run(Probe(engine), arrivals, args.seconds)
        e2e = serve_cell.e2e_metrics(w)
        wait3 = thirds(waiting, w.t0, w.seconds)
        offered = rate * sum(a.max_new_tokens for a in arrivals) \
            / len(arrivals)
        ok = sustained(wait3)
        missed |= not ok
        if not missed:
            knee = rate
        misses = 0 if ok else misses + 1
        print(json.dumps({
            "rate": rate, "sustained": ok,
            "requests_due": len(w.requests),
            "held_by_third": thirds(held, w.t0, w.seconds),
            "waiting_by_third": wait3,
            "waiting_at_close": len(engine.pending),
            "slots_at_close": len(engine.slots),
            "offered_tokens_per_s": offered, **e2e}), flush=True)
        if misses == 2:
            break
    print(json.dumps({"knee": knee}), flush=True)


if __name__ == "__main__":
    main()
