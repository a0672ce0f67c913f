"""Per-host page-pool topology for paged serving (DESIGN.md §16).

A multi-host deployment does not share one page pool: each host owns a
pool sized to its HBM, its own page table, and its own batch slots. The
:class:`ShardedPagedEngine` models exactly that — N per-host
:class:`~repro.serve.engine.PagedEngine` instances (host-sharded pools +
sharded page tables) behind one request surface, with batch admission over
the data axis: each incoming request is placed on the host with the most
free pages (ties: fewest queued requests, then lowest host id — a
deterministic least-loaded rule, the data-parallel analogue of the
single-engine least-slot admission).

Everything downstream of placement is the unmodified single-host engine,
so per-host behaviour (preemption, prefix caching, chunked prefill,
speculation) and results stay bitwise-identical to running that host's
request stream through a standalone PagedEngine.
"""
from __future__ import annotations

from typing import Optional

from repro import obs
from .engine import PagedEngine, Request


class ShardedPagedEngine:
    """Data-axis sharded paged serving: one PagedEngine per host.

    ``n_hosts`` is the data-axis extent (host count). All other keyword
    arguments are forwarded to every per-host :class:`PagedEngine` — each
    host gets its own ``batch_slots`` and ``n_pages`` pool, so the
    aggregate capacity is ``n_hosts ×`` the single-engine figures.
    """

    def __init__(self, model, params, *, n_hosts: int = 2,
                 rng=None, **engine_kw):
        if n_hosts < 1:
            raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
        self.n_hosts = n_hosts
        if rng is not None:
            engine_kw["rng"] = rng
        self.hosts = [PagedEngine(model, params, **engine_kw)
                      for _ in range(n_hosts)]
        self.placements: dict[int, int] = {}    # uid -> host id
        self.admissions_by_host = [0] * n_hosts

    # -- admission over the data axis ------------------------------------

    def _place(self) -> int:
        """Deterministic least-loaded host: most free pages, then fewest
        queued requests, then lowest id."""
        def load(i: int):
            h = self.hosts[i]
            return (-h.alloc.free_pages, len(h.pending), i)
        return min(range(self.n_hosts), key=load)

    def submit(self, req: Request) -> None:
        host = self._place()
        if req.uid in self.placements:
            raise ValueError(f"request {req.uid} already submitted "
                             f"(host {self.placements[req.uid]})")
        self.hosts[host].submit(req)
        self.placements[req.uid] = host
        self.admissions_by_host[host] += 1
        obs.incr("sharded_engine.submitted")

    # -- stepping / results ----------------------------------------------

    def step(self) -> bool:
        """Advance every host one step; True while any host has work."""
        busy = False
        for h in self.hosts:
            # note: no short-circuit — every host steps every tick
            busy = h.step() or busy
        return busy

    @property
    def results(self) -> dict:
        merged: dict = {}
        for h in self.hosts:
            merged.update(h.results)
        return merged

    def run(self) -> dict:
        while self.step():
            pass
        return self.results

    # -- reporting --------------------------------------------------------

    def report(self) -> dict:
        """Aggregate metrics plus the per-host reports — the cross-host
        balance (admissions_by_host spread) is the health signal."""
        per_host = [h.report() for h in self.hosts]
        agg = {k: sum(r[k] for r in per_host)
               for k in ("steps", "admissions", "preemptions",
                         "tokens_generated", "completed", "page_pool_size")}
        agg["n_hosts"] = self.n_hosts
        agg["admissions_by_host"] = list(self.admissions_by_host)
        agg["placements"] = dict(self.placements)
        agg["per_host"] = per_host
        return agg
