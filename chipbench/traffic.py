"""The one general generator: a traffic file of ``traffic/`` in, requests or
batches out. A mix's sizes and gaps are stratified quantiles of the stated
distributions (the gaps those of an exponential at the mix's rate), paired
and ordered once by the mix's own ``order_seed``, so every run serves the
same schedule; the run's seed draws the token ids. Two seeds do the same
work on different tokens."""
from __future__ import annotations

import dataclasses
import json
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def load(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, *stream])


def lognormal_quantiles(p: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a log-normal (median, sigma),
    rounded and clipped to [min, max]."""
    z = np.array([_NORMAL.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(p["median"] * np.exp(p["sigma"] * z)).astype(np.int64)
    return np.clip(x, p["min"], p["max"])


@dataclasses.dataclass
class Arrival:
    uid: int
    due_s: float            # offset from the window's start
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def open_loop(mix: dict, seconds: float, seed: int, vocab: int,
              rate: float | None = None,
              lead: float | None = None) -> list[Arrival]:
    """Arrivals at ``rate`` (default: the mix's) from ``lead`` seconds
    before the window (default: the mix's ``lead_seconds``) to its close:
    n = round(rate * (lead + seconds)) requests whose gaps are the
    stratified quantiles of an exponential, scaled to sum to n / rate;
    those due after the close (the rounding of n can leave one) are
    dropped. ``due_s`` is the offset from the window's start, negative in
    the lead."""
    rate = mix["rate_per_s"] if rate is None else rate
    lead = mix.get("lead_seconds", 0.0) if lead is None else lead
    n = max(1, int(round(rate * (lead + seconds))))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps *= (n / rate) / gaps.sum()
    plen = lognormal_quantiles(mix["prompt_tokens"], n)
    olen = lognormal_quantiles(mix["output_tokens"], n)
    order = rng_for(mix.get("order_seed", 0), 1)
    gaps, plen, olen = (order.permutation(a) for a in (gaps, plen, olen))
    due = np.cumsum(gaps) - gaps[0] * 0.5 - lead
    rng = rng_for(seed, 1)
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, int(plen[i]), dtype=np.int64)
        out.append(Arrival(i, float(due[i]), prompt.astype(np.int32),
                           int(olen[i])))
    return [a for a in out if a.due_s < seconds]


class PackedDocs:
    """Training rows of packed documents: each document starts at a random
    token and counts up by ``step`` (mod V), a share ``noise`` of tokens is
    uniform; documents have geometric lengths; no loss where a target
    starts a new document. Batch k depends on (seed, k) alone."""

    def __init__(self, mix: dict, batch: int, seed: int, vocab: int,
                 step: int = 7):
        self.mix, self.batch, self.seed = mix, batch, seed
        self.vocab, self.inc = vocab, step
        self.index = 0

    def batch_at(self, k: int) -> dict:
        s1 = self.mix["seq_len"] + 1
        rng = rng_for(self.seed, 2, k)
        starts = rng.random((self.batch, s1)) < 1.0 / self.mix["mean_doc_len"]
        starts[:, 0] = True
        doc = np.cumsum(starts, axis=1) - 1
        first = np.maximum.accumulate(
            np.where(starts, np.arange(s1), 0), axis=1)
        base = rng.integers(0, self.vocab, (self.batch, s1))
        base = np.take_along_axis(base, doc, axis=1)
        toks = (base + self.inc * (np.arange(s1) - first)) % self.vocab
        noise = rng.random((self.batch, s1)) < self.mix["noise"]
        toks = np.where(noise, rng.integers(0, self.vocab, toks.shape), toks)
        toks = toks.astype(np.int32)
        return {"inputs": toks[:, :-1], "targets": toks[:, 1:],
                "loss_mask": (~starts[:, 1:]).astype(np.float32)}

    def tokens_per_batch(self) -> int:
        return self.batch * self.mix["seq_len"]

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        import jax.numpy as jnp
        b = {k: jnp.asarray(v) for k, v in self.batch_at(self.index).items()}
        self.index += 1
        return b


def describe_lengths(arrivals: list[Arrival]) -> dict:
    p = [len(a.prompt) for a in arrivals]
    o = [a.max_new_tokens for a in arrivals]
    return {"requests": len(arrivals), "prompt_median": float(np.median(p)),
            "output_median": float(np.median(o)),
            "prompt_mean": float(np.mean(p)), "output_mean": float(np.mean(o)),
            "first_due_s": min(a.due_s for a in arrivals),
            "last_due_s": max(a.due_s for a in arrivals)}
