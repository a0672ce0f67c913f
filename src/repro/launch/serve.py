"""Serving launcher: continuous batching through :class:`PagedEngine`.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --requests 16 --prompt-len 32 --new-tokens 16

On a TPU pass ``--kernels pallas_tpu``.
"""
from __future__ import annotations

import argparse

import numpy as np
import jax

from repro.configs import get_config
from repro.models import build_model
from repro.serve import PagedEngine, Request
from repro.kernels.modes import MODES
from repro.util import enable_compile_cache


def make_requests(vocab_size: int, n: int, prompt_lens, new_tokens: int,
                  seed: int = 0) -> list:
    """``n`` greedy requests; prompt lengths drawn from ``prompt_lens``."""
    rng = np.random.default_rng(seed)
    return [Request(uid, rng.integers(0, vocab_size, int(rng.choice(
        prompt_lens))).astype(np.int32), new_tokens, temperature=0.0)
        for uid in range(n)]


def serve(model, params, requests, *, batch_slots: int,
          page_size: int = 64, **engine_kwargs) -> PagedEngine:
    """Serve ``requests`` to completion; the engine holds the results
    (``engine.results``) and its metrics (``engine.report()``).
    ``engine_kwargs`` go to :class:`PagedEngine`."""
    longest = max(len(r.prompt) + r.max_new_tokens for r in requests)
    engine = PagedEngine(model, params, batch_slots=batch_slots,
                         page_size=page_size,
                         max_pages_per_seq=-(-longest // page_size),
                         **engine_kwargs)
    for r in requests:
        engine.submit(r)
    engine.run()
    return engine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--kernels", default="reference",
                    choices=MODES)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=4)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg, mode=args.kernels)
    params = model.init(jax.random.PRNGKey(0))
    lens = range(args.prompt_len // 2, args.prompt_len + 1)
    requests = make_requests(cfg.vocab_size, args.requests, lens,
                             args.new_tokens)
    engine = serve(model, params, requests, batch_slots=args.batch_size)
    print(f"[serve] served {len(engine.results)} requests on "
          f"{jax.devices()[0].platform} ({args.kernels}): {engine.report()}")
    for uid in sorted(engine.results)[:4]:
        print(f"  req {uid}: {engine.results[uid][-args.new_tokens:]}")


if __name__ == "__main__":
    main()
