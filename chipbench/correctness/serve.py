"""Served tokens against the reference: after the window, a sample drawn
from the seed of the requests the engine finished (the one with the most
served tokens always in it, and at least MIN_REQUESTS, so several slots) is run through the reference once, each prompt
with its served tokens, and each served token's reference logit is read
against the reference's best at that position. Greedy serving with exact
arithmetic reads 0; a wrong token reads the distance to the best.

The number compared is the widest of those gaps (``served_logit_gap``).
The control is the same reading for the token the fp8 reference ranks
first at each position (``control_logit_gap``)."""
from __future__ import annotations

import numpy as np

MIN_TOKENS = 300
MIN_REQUESTS = 4
MAX_REQUESTS = 12


def sample(finished: dict, prompt_lens: dict, seed: int) -> list:
    """uids: the longest output first, then others in an order drawn from
    the seed, until both MIN_TOKENS served tokens and MIN_REQUESTS, or
    MAX_REQUESTS."""
    from ..traffic import rng_for
    uids = sorted(finished)
    if not uids:
        return []
    out_len = {u: len(finished[u]) - prompt_lens[u] for u in uids}
    longest = max(uids, key=lambda u: (out_len[u], -u))
    rest = [u for u in uids if u != longest]
    rest = [rest[i] for i in rng_for(seed, 3).permutation(len(rest))]
    pick, tokens = [longest], out_len[longest]
    for u in rest:
        if (tokens >= MIN_TOKENS and len(pick) >= MIN_REQUESTS) \
                or len(pick) >= MAX_REQUESTS:
            break
        pick.append(u)
        tokens += out_len[u]
    return pick


def gaps(spec, w, finished: dict, prompt_lens: dict, uids: list,
         max_seq: int, quant=None) -> tuple[np.ndarray, np.ndarray]:
    """Per served token of ``uids``: (gap of the served token, gap of the
    ``quant`` reference's first choice)."""
    import jax.numpy as jnp
    from .. import reference
    served_all, ctrl_all = [], []
    max_out = max_seq // 2
    for u in uids:
        seq = np.asarray(finished[u], np.int32)
        plen, n = prompt_lens[u], len(seq) - prompt_lens[u]
        if len(seq) > max_seq or n > max_out:
            raise ValueError(f"request {u}: {len(seq)} tokens, {n} served")
        toks = np.zeros(max_seq, np.int32)
        toks[:len(seq)] = seq
        rows = np.zeros(max_out, np.int32)
        served = np.zeros(max_out, np.int32)
        rows[:n] = plen - 1 + np.arange(n)
        served[:n] = seq[plen:]
        g, c = reference.served_gaps(spec, w, jnp.asarray(toks),
                                     jnp.asarray(rows), jnp.asarray(served),
                                     quant)
        served_all.append(np.asarray(g)[:n])
        ctrl_all.append(np.asarray(c)[:n])
    if not uids:
        return np.zeros(0), np.zeros(0)
    return np.concatenate(served_all), np.concatenate(ctrl_all)
