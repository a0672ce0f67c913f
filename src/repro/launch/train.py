"""Training launcher.

Runs ``train_loop`` on whatever JAX finds: ``--kernels pallas_tpu`` on a
TPU, ``reference`` or ``pallas_interpret`` (``--smoke`` sizes) on a CPU.

  PYTHONPATH=src python -m repro.launch.train --arch llama-100m \
      --steps 200 --batch 8 --seq 512 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import get_config
from repro.models import build_model
from repro.data.pipeline import DataConfig, DataIterator
from repro.optim import AdamWConfig, cosine_schedule, wsd_schedule
from repro.train import train_loop, FailureInjector, StragglerWatchdog
from repro.launch.mesh import make_host_mesh
from repro.kernels.modes import MODES
from repro.util import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default="cosine")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--kernels", default="reference",
                    choices=MODES)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated node failures at these steps")
    ap.add_argument("--mesh", action="store_true",
                    help="train data-parallel over all local devices")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    sched = (wsd_schedule if args.schedule == "wsd" else cosine_schedule)(
        args.lr, args.warmup, args.steps)
    opt = AdamWConfig(schedule=sched)

    mesh = make_host_mesh() if args.mesh else None
    model = build_model(cfg, mode=args.kernels, mesh=mesh)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    it = DataIterator(dcfg, mesh=mesh)

    res = train_loop(
        model, it, args.steps, opt, mesh=mesh, zero1=args.zero1,
        grad_compress=args.grad_compress, microbatches=args.microbatches,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        failure_injector=FailureInjector(tuple(args.fail_at)),
        watchdog=StragglerWatchdog())
    print(f"[train] finished: {len(res.losses)} steps, "
          f"first loss {res.losses[0]:.4f}, last loss {res.losses[-1]:.4f}, "
          f"restarts {res.restarts}, stragglers {len(res.straggler_events)}")


if __name__ == "__main__":
    main()
