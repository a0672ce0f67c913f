"""A training cell: the configuration's model trained by the program's
``train_loop`` on the cell's packed-document feed. The loop runs from the
first step to the close of the window in one call: its first WARM steps
(compile, and the correctness probe's reads) are set-up, the rest are the
window."""
from __future__ import annotations

import gc
import math
import time

import jax

from . import system, traffic, weights
from .correctness import train as check

WARM = check.STEPS + 1


class WindowSteps:
    """Stands in for ``train_loop``'s step count: the loop asks
    ``step < num_steps`` after each step has read its loss back, so the
    answer is given by the clock. The first WARM steps are set-up; the
    window then runs ``seconds`` and ends at a step boundary."""

    def __init__(self, seconds, on_start, trace_from=None, tracer=None,
                 clock=time.perf_counter):
        self.seconds, self.on_start, self.clock = seconds, on_start, clock
        self.trace_from, self.tracer = trace_from, tracer
        self.t0 = self.t1 = None
        self.first = self.last = WARM
        self.traced_from = -1

    def __gt__(self, step: int) -> bool:
        if step < WARM:
            return True
        now = self.clock()
        if self.t0 is None:
            self.t0 = now
            self.on_start()
        self.t1, self.last = now, step
        if (self.trace_from is not None and self.traced_from < 0
                and now - self.t0 >= self.trace_from):
            self.tracer.start()
            self.traced_from = step
        if now - self.t0 < self.seconds:
            return True
        if self.traced_from >= 0:
            self.tracer.stop()
        return False

    @property
    def steps(self) -> int:
        return self.last - self.first

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0


class Feed(traffic.PackedDocs):
    """The packed-document rows, each fetch marked in a traced run."""

    def __init__(self, *a, annotate=False, **k):
        super().__init__(*a, **k)
        self.annotate = annotate

    def __next__(self):
        if not self.annotate:
            return super().__next__()
        with jax.profiler.TraceAnnotation("chipbench.train.data"):
            return super().__next__()


OPT_KEYS = ("learning_rate", "b1", "b2", "eps", "weight_decay",
            "clip_norm")


def program(spec, mix, seed: int, mode: str, steps, log, *,
            annotate: bool = False):
    """``train_loop`` from the seed's weights on the seed's rows, for as
    many steps as ``steps`` allows; returns (losses, the probe's
    readings, the feed)."""
    from repro.optim import AdamWConfig, constant_schedule
    from repro.train import trainer
    hp = spec.raw["train"]
    model = system.build(spec, mode)
    params = system.program_params(spec, weights.make(spec, seed), model)
    model.init = lambda rng: params     # the benchmark's, not the program's
    opt = AdamWConfig(schedule=constant_schedule(hp["learning_rate"]),
                      b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                      weight_decay=hp["weight_decay"],
                      clip_norm=hp["clip_norm"])
    probe = check.StepProbe(spec, seed, hp["b1"])
    feed = Feed(mix, hp["micro_batch"], seed, spec.vocab, annotate=annotate)
    make = trainer.make_train_step
    trainer.make_train_step = probe.wrap(make)
    try:
        res = trainer.train_loop(model, feed, steps, opt, log_every=0,
                                 log=log)
    finally:
        trainer.make_train_step = make
    losses = list(res.losses)
    del res, params, model
    gc.collect()
    prog = probe.readings()
    prog["losses"] = losses[:check.STEPS]
    return losses, prog, feed


def reference(spec, seed: int, feed, quant=None) -> dict:
    hp = spec.raw["train"]
    batches = [feed.batch_at(k) for k in range(check.STEPS)]
    return check.reference_readings(spec, {k: hp[k] for k in OPT_KEYS},
                                    batches, seed, quant)


def run(ctx) -> dict:
    spec, mix = ctx.spec, ctx.mix
    steps = WindowSteps(ctx.seconds, ctx.mark_setup, ctx.trace_from,
                        ctx.tracer)
    losses, prog, feed = program(spec, mix, ctx.seed, ctx.mode, steps,
                                 ctx.log, annotate=ctx.trace_from is not None)
    ctx.read_memory()           # the process's peak, before the reference
    window = {"steps": steps.steps, "seconds": steps.window_s,
              "tokens": steps.steps * feed.tokens_per_batch(),
              "seq_len": mix["seq_len"], "traced_from": steps.traced_from}
    ctx.log(f"window: {steps.steps} steps in {steps.window_s:.3f} s; "
            f"losses {losses[:WARM]} ... {losses[-1:]}")
    t = time.perf_counter()
    ref = reference(spec, ctx.seed, feed)
    cmp = check.compare(prog, ref)
    ctx.log(f"reference: losses {ref['losses']} vs {prog['losses']}; "
            f"{cmp}; {time.perf_counter() - t:.1f} s")
    del prog, ref
    readings = {k: cmp[k] for k in check.NUMBERS}
    failed = sum(not math.isfinite(x) for x in losses[WARM:])
    return {"window": window, "readings": readings,
            "attempted": steps.steps, "failed": failed,
            "e2e": {"train_tokens_per_s": window["tokens"]
                    / window["seconds"]}}
