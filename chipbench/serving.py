"""Open-loop serving: requests are submitted to the program's PagedEngine
when they fall due, whether or not the engine keeps up, and every token is
stamped by the host clock after the ``step()`` that produced it (the step
reads the sampled tokens back, so the stamp is after the device finished).
"""
from __future__ import annotations

import dataclasses
import time
from contextlib import nullcontext

from .traffic import Arrival


@dataclasses.dataclass
class ReqRecord:
    uid: int
    due: float                  # host clock
    prompt_len: int
    max_new: int
    submitted: float = -1.0
    slotted: float = -1.0       # first seen holding a slot
    token_times: list = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    decode_ctx: list            # positions attended by each decoding slot
    chunks: list                # (start, tokens) of each prefill chunk
    new_tokens: int


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    requests: dict              # uid -> ReqRecord (those due in the window)
    served: dict                # uid -> ReqRecord (all, the lead's too)
    steps: list                 # StepRecord, of the steps in the window
    late_s: list                # submit time - due, per submitted request
    batch_slots: int
    chunk_tokens: int
    refused: int = 0
    traced_steps: int = -1      # index of the first step in the trace

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def token_times(self):
        """Per request served, the stamps of its tokens in the window."""
        for r in self.served.values():
            yield [t for t in r.token_times if t >= self.t0]


def engine_kwargs(serve: dict, mix: dict) -> dict:
    page = serve["page_size"]
    return dict(batch_slots=serve["batch_slots"], page_size=page,
                max_pages_per_seq=serve["max_seq_tokens"] // page,
                n_pages=serve["kv_pool_pages"] + 1,
                chunk_tokens=serve["chunk_tokens"],
                prefix_cache=bool(mix.get("prefix_cache", False)),
                max_cached_buckets=64)


def annotate(trace: bool, name: str):
    if not trace:
        return nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _snapshot(engine) -> dict:
    """uid -> (prompt length as served, prefill cursor, tokens generated)
    for every request holding a slot."""
    return {r.req.uid: (len(r.req.prompt), r.prefill_cursor,
                        len(r.generated)) for r in engine.slots.values()}


def observe(engine, before: dict, recs: dict, now: float) -> StepRecord:
    """What the step just made: stamp every new token with ``now`` and work
    out the launches it ran from the slots before and after it. A
    preempted request comes back with its tokens so far appended to its
    prompt, so its output count is (prompt as served - prompt) + generated.
    """
    after = _snapshot(engine)
    ctx, chunks, new = [], [], 0
    retired = [u for u in engine.results
               if u not in after and u in recs and not recs[u].done]
    for uid in list(after) + retired:
        rec = recs.get(uid)
        if rec is None:
            continue
        if uid in after:
            plen, cur, gen = after[uid]
        else:                                   # retired in this step
            rec.done = True
            plen, cur = before.get(uid, (rec.prompt_len,))[0], -1
            gen = len(engine.results[uid]) - plen
        if rec.slotted < 0:
            rec.slotted = now
        k = plen - rec.prompt_len + gen - len(rec.token_times)
        if k > 0:
            rec.token_times.extend([now] * k)
            new += k
        b_plen, b_cur, _ = before.get(uid, (plen, 0, 0))
        if b_plen == plen and b_cur >= 0:       # a chunk ran
            stop = cur if cur >= 0 else plen
            if stop > b_cur:
                chunks.append((b_cur, stop - b_cur))
            if cur < 0:
                k -= 1                          # first token, off the chunk
        if k > 0:                               # it decoded once
            ctx.append(plen + gen - 1)
    return StepRecord(0.0, now, ctx, chunks, new)


def run(engine, arrivals: list[Arrival], seconds: float, *,
        on_open=None, trace_from: float | None = None, tracer=None,
        clock=time.perf_counter) -> Window:
    """Serve ``arrivals`` and measure ``seconds``. Those due before offset
    0 (the mix's lead) are served first, untimed, so that the window opens
    on an engine already holding the traffic's own occupancy; it opens at
    the first step boundary at or after offset 0, where ``on_open`` is
    called. With ``trace_from``, ``tracer`` is started at the first step
    boundary that many seconds into the window and stopped at its close."""
    from repro.serve.engine import Request

    queue = sorted((a for a in arrivals if a.due_s < seconds),
                   key=lambda a: a.due_s)
    zero = clock() + max(0.0, -min((a.due_s for a in queue), default=0.0))
    recs = {a.uid: ReqRecord(a.uid, zero + a.due_s, len(a.prompt),
                             a.max_new_tokens) for a in queue}
    steps, late = [], []
    i, refused, traced = 0, 0, -1
    t0, end = None, float("inf")
    trace = trace_from is not None
    while True:
        now = clock()
        if t0 is None and now >= zero:
            t0, end = now, now + seconds
            if on_open is not None:
                on_open()
        if now >= end:
            break
        if trace and traced < 0 and t0 is not None \
                and now - t0 >= trace_from:
            tracer.start()
            traced = len(steps)
        while i < len(queue) and recs[queue[i].uid].due <= now:
            a = queue[i]
            try:
                engine.submit(Request(a.uid, a.prompt, a.max_new_tokens,
                                      temperature=0.0))
            except ValueError:
                refused += a.due_s >= 0     # those due in the window
            recs[a.uid].submitted = now
            late.append(now - recs[a.uid].due)
            i += 1
        if engine.slots or engine.pending:
            before = _snapshot(engine)
            s0 = clock()
            with annotate(traced >= 0, "chipbench.serve.step"):
                engine.step()
            step = observe(engine, before, recs, clock())
            step.t0 = s0
            if t0 is not None:
                steps.append(step)
        else:
            nxt = recs[queue[i].uid].due if i < len(queue) else end
            wake = min(nxt, end if t0 is not None else zero)
            with annotate(traced >= 0, "chipbench.serve.idle"):
                time.sleep(max(0.0, wake - clock()))
    t1 = clock()
    if traced >= 0:
        tracer.stop()
    due = {u: r for u, r in recs.items() if r.due >= zero}
    return Window(t0, t1, due, recs, steps, late, engine.batch_slots,
                  engine.chunk_tokens, refused, traced)


def drain(engine, seconds: float, clock=time.perf_counter) -> None:
    """After the window: let the engine go on (no new arrivals) for at
    most ``seconds``, so that the requests in flight at the close can
    finish and be compared. Untimed."""
    deadline = clock() + seconds
    while (engine.slots or engine.pending) and clock() < deadline:
        engine.step()
