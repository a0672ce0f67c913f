"""A serving cell: the configuration's model in the program's PagedEngine,
driven open loop by the cell's traffic mix (``serving``)."""
from __future__ import annotations

import gc
import time

import numpy as np

from . import serving, system, traffic, weights
from .correctness import serve as check

DRAIN_SECONDS = 20.0     # after the close, for the requests in flight


def warm(engine, vocab: int, page: int, chunk: int, max_seq: int) -> None:
    """Compile, or load from the compile cache, every program the window
    can reach, through the engine's public surface: for each decode
    page-count bucket a request that decodes in it alone and again beside
    a request in mid-prefill (the masked launch), one that grows a page,
    and the one chunk-prefill program."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(0)

    def req(uid, plen, new):
        return Request(uid, rng.integers(0, vocab, plen).astype(np.int32),
                       new, temperature=0.0)

    pages, uid = 1, -1
    while pages * page <= max_seq:
        a = req(uid, pages * page - 3, 3)
        engine.submit(a)
        while any(s.req.uid == uid and s.prefilling
                  for s in engine.slots.values()) or engine.pending:
            engine.step()
        engine.submit(req(uid - 1, chunk + 1, 2))
        while engine.step():
            pass
        uid -= 2
        pages *= 2
    engine.submit(req(uid, page - 1, 3))            # grows a page
    while engine.step():
        pass
    # admission writes a request's pages into its page-table row through
    # an eager scatter compiled once per page count: warm every count a
    # prompt (or a preempted request's continuation) can have
    from repro.serve import kv_cache
    state = kv_cache.init_page_state(engine.batch_slots,
                                     engine.max_pages_per_seq)
    for n in range(1, engine.max_pages_per_seq + 1):
        kv_cache.assign_slot(state, 0, list(range(1, n + 1)), n * page)


def build(spec, mix, seed: int, mode: str):
    """The program's engine over the model with the seed's weights,
    warmed."""
    from repro.serve.engine import PagedEngine
    serve = spec.raw["serve"]
    model = system.build(spec, mode)
    params = system.program_params(spec, weights.make(spec, seed), model)
    engine = PagedEngine(model, params,
                         **serving.engine_kwargs(serve, mix))
    warm(engine, spec.vocab, serve["page_size"], serve["chunk_tokens"],
         serve["max_seq_tokens"])
    return engine


def compare(spec, seed: int, finished: dict, plens: dict, quant=None):
    """Served-token gaps (and the ``quant`` control's) over the seed's
    sample of finished requests, with the reference's own weights."""
    uids = check.sample(finished, plens, seed)
    w = weights.make(spec, seed)
    served, control = check.gaps(spec, w, finished, plens, uids,
                                 spec.raw["serve"]["max_seq_tokens"], quant)
    return uids, served, control


def drain(engine, window) -> dict:
    """Let the requests in flight at the close finish (untimed); then the
    finished requests that the window served tokens of, uid -> tokens."""
    serving.drain(engine, DRAIN_SECONDS)
    in_window = {u for u, r in window.served.items()
                 if r.token_times and r.token_times[-1] >= window.t0}
    return {u: r for u, r in engine.results.items() if u in in_window}


def run(ctx) -> dict:
    spec, mix = ctx.spec, ctx.mix
    engine = build(spec, mix, ctx.seed, ctx.mode)
    arrivals = traffic.open_loop(mix, ctx.seconds, ctx.seed, spec.vocab)
    ctx.log(f"traffic: {traffic.describe_lengths(arrivals)}")
    window = serving.run(engine, arrivals, ctx.seconds,
                         on_open=ctx.mark_setup, trace_from=ctx.trace_from,
                         tracer=ctx.tracer)
    ctx.read_memory()
    ctx.log(f"engine: {engine.report()}; at the close {len(engine.slots)} "
            f"in slots, {len(engine.pending)} waiting")
    finished = drain(engine, window)
    del engine
    gc.collect()

    t = time.perf_counter()
    plens = {a.uid: len(a.prompt) for a in arrivals}
    uids, served, _ = compare(spec, ctx.seed, finished, plens)
    readings = {"served_logit_gap": float(served.max()) if len(served)
                else float("nan")}
    ctx.log(f"reference over {len(uids)} requests, {len(served)} served "
            f"tokens, {time.perf_counter() - t:.1f} s")
    return {"window": window, "readings": readings,
            "attempted": len(window.requests), "failed": window.refused,
            "e2e": e2e_metrics(window)}


def e2e_metrics(w) -> dict:
    """Time to first token over the requests due in the window (one with
    none by the close counts the close); every gap between two tokens of
    one request inside the window; the tokens emitted in it."""
    ttft = [(r.token_times[0] if r.token_times else w.t1) - r.due
            for r in w.requests.values()]
    gaps, tokens = [], 0
    for times in w.token_times():
        gaps.extend(np.diff(times))
        tokens += len(times)
    out = {"ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
           "output_tokens_per_s": tokens / w.seconds}
    if gaps:
        out["itl_p95_ms"] = float(np.percentile(gaps, 95)) * 1e3
    return out
