"""Train-step factory + fault-tolerant training loop.

``make_train_step`` builds the jitted step: microbatched grad accumulation
(lax.scan — keeps the backward of microbatch k overlappable with the grad
reduce-scatter of k-1 under XLA's latency-hiding scheduler), optional int8
error-feedback gradient compression, AdamW, donated state.

``train_loop`` adds the operational layer: checkpoint/restart (async, atomic),
failure injection → restore-latest recovery, straggler watchdog.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import autotune
from repro.optim import AdamWConfig, adamw_update, ef_compress
from repro.distributed.sharding import batch_specs
from . import checkpoint as ckpt_lib
from .state import init_state, sharded_init, state_shardings


class SimulatedFailure(RuntimeError):
    """Raised by the failure injector to emulate a node loss."""


@dataclasses.dataclass
class FailureInjector:
    fail_at_steps: tuple[int, ...] = ()
    _fired: set = dataclasses.field(default_factory=set)

    def maybe_fail(self, step: int) -> None:
        if step in self.fail_at_steps and step not in self._fired:
            self._fired.add(step)
            raise SimulatedFailure(f"injected node failure at step {step}")


@dataclasses.dataclass
class StragglerWatchdog:
    """Flags steps slower than ``factor``× the running median and invokes a
    mitigation hook (on a real fleet: re-shard away from the slow host; here:
    record + notify)."""
    factor: float = 3.0
    warmup: int = 5
    durations: list = dataclasses.field(default_factory=list)
    events: list = dataclasses.field(default_factory=list)
    on_straggler: Optional[Callable[[int, float, float], None]] = None

    def observe(self, step: int, seconds: float) -> bool:
        self.durations.append(seconds)
        if len(self.durations) <= self.warmup:
            return False
        med = sorted(self.durations)[len(self.durations) // 2]
        if seconds > self.factor * med:
            self.events.append((step, seconds, med))
            if self.on_straggler:
                self.on_straggler(step, seconds, med)
            return True
        return False


def _split_microbatches(batch: dict, n: int) -> dict:
    return jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def make_train_step(model, opt_cfg: AdamWConfig, *, mesh=None,
                    zero1: bool = True, grad_compress: bool = False,
                    microbatches: int = 1, donate: bool = True):
    """Returns a jitted (state, batch) -> (state, metrics) function."""

    def step_fn(state, batch):
        def loss_fn(params, mb):
            loss, metrics = model.loss(params, mb)
            return loss, metrics

        if microbatches > 1:
            mbs = _split_microbatches(batch, microbatches)

            def accum(carry, mb):
                gsum, lsum = carry
                (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    state["params"], mb)
                gsum = jax.tree.map(jnp.add, gsum,
                                    jax.tree.map(lambda g: g.astype(jnp.float32), grads))
                return (gsum, lsum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (gsum, lsum), _ = jax.lax.scan(accum, (zeros, jnp.zeros(())), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
            metrics = {"ce": loss, "aux": jnp.zeros(())}
        else:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch)

        new_state = dict(state)
        if grad_compress:
            grads, new_ef = ef_compress(grads, state["ef"])
            new_state["ef"] = new_ef

        new_params, new_opt, om = adamw_update(opt_cfg, grads,
                                               state["opt"], state["params"])
        new_state.update(params=new_params, opt=new_opt,
                         step=state["step"] + 1)
        return new_state, {"loss": loss, **metrics, **om}

    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    sh = state_shardings(model, mesh, zero1=zero1,
                         grad_compress=grad_compress)
    abs_batch = None  # batch shardings applied by caller via device_put
    return jax.jit(step_fn, in_shardings=(sh, None),
                   out_shardings=(sh, None),
                   donate_argnums=(0,) if donate else ())


@dataclasses.dataclass
class TrainLoopResult:
    state: dict
    losses: list
    restarts: int
    straggler_events: list
    # {(batch, seq): {op: KernelPolicy}} — one entry per compiled bucket
    policies: dict = dataclasses.field(default_factory=dict)


def pin_bucket_policies(model, batch: dict, pinned: dict,
                        log: Callable = print, mesh=None) -> dict:
    """Resolve + pin the kernel policies for this batch's compiled bucket.

    XLA compiles one step function per input shape; the autotuner memoizes
    one policy set per shape-bucket — pinning here makes the pairing
    explicit and reproducible in the training log (DESIGN.md §5). With a
    ``mesh`` carrying a model axis, the plan decisions are scored with the
    sharded collective chain term (DESIGN.md §16) — a different sharding is
    a different bucket, the same way a different dtype is.
    """
    inputs = batch.get("inputs") if isinstance(batch, dict) else batch
    if inputs is None or getattr(inputs, "ndim", 0) < 2:
        return pinned
    key = (int(inputs.shape[0]), int(inputs.shape[1]))
    if key not in pinned:
        from repro.distributed.sharding import train_shard_spec

        shard = train_shard_spec(model.cfg, mesh)
        pols = autotune.policies_for_model(model.cfg, batch=key[0],
                                           seq_len=key[1], shard=shard)
        pinned[key] = pols
        if obs.enabled():   # guard: no f-string on the disabled path
            obs.incr("trainer.bucket_pins")
            obs.incr(f"trainer.bucket_pins.{key[0]}x{key[1]}")
        desc = "; ".join(f"{op}={p.schedule.name}{tuple(p.describe()['blocks'])}"
                         for op, p in sorted(pols.items()))
        log(f"[trainer] bucket {key}: pinned kernel policies "
            f"{desc or '(none)'}")
    return pinned


def train_loop(model, data_iter, num_steps: int, opt_cfg: AdamWConfig, *,
               rng=None, mesh=None, zero1: bool = False,
               grad_compress: bool = False, microbatches: int = 1,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
               failure_injector: Optional[FailureInjector] = None,
               watchdog: Optional[StragglerWatchdog] = None,
               max_restarts: int = 3, log_every: int = 10,
               pretuned=None,
               log: Callable = print) -> TrainLoopResult:
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if pretuned is not None:
        # calibrated policy table (path or report dict); installed before
        # the first bucket pin so pinned policies come from measurement
        autotune.use_pretuned(pretuned)
    step_fn = make_train_step(model, opt_cfg, mesh=mesh, zero1=zero1,
                              grad_compress=grad_compress,
                              microbatches=microbatches)

    def fresh_state():
        if mesh is not None:
            return sharded_init(model, rng, mesh, zero1=zero1,
                                grad_compress=grad_compress)
        return init_state(model, rng, grad_compress=grad_compress)

    checkpointer = (ckpt_lib.AsyncCheckpointer(ckpt_dir)
                    if ckpt_dir is not None else None)

    # resume if a valid checkpoint exists
    state = None
    if ckpt_dir is not None and ckpt_lib.available_steps(ckpt_dir):
        template = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
            if hasattr(a, "shape") else a, jax.eval_shape(fresh_state))
        sh = (state_shardings(model, mesh, zero1=zero1,
                              grad_compress=grad_compress)
              if mesh is not None else None)
        state, step0 = ckpt_lib.restore(ckpt_dir, template, shardings=sh)
        data_iter.load_state_dict({"step": step0})
        log(f"[trainer] resumed from checkpoint at step {step0}")
    if state is None:
        state = fresh_state()

    losses: list = []
    restarts = 0
    pinned_policies: dict = {}
    step = int(jax.device_get(state["step"]))
    while step < num_steps:
        try:
            with obs.span("trainer.step", step=step):
                with obs.span("trainer.data"):
                    batch = next(data_iter)
                    pin_bucket_policies(model, batch, pinned_policies,
                                        log=log, mesh=mesh)
                t0 = time.perf_counter()
                if failure_injector is not None:
                    failure_injector.maybe_fail(step)
                with obs.span("trainer.dispatch"):
                    state, metrics = step_fn(state, batch)
                with obs.span("trainer.loss_read"):
                    loss = float(jax.device_get(metrics["loss"]))
            dt = time.perf_counter() - t0
            obs.incr("trainer.steps")
            if watchdog is not None:
                watchdog.observe(step, dt)
            losses.append(loss)
            step += 1
            if log_every and step % log_every == 0:
                log(f"[trainer] step {step:5d} loss {loss:.4f} "
                    f"({dt*1e3:.0f} ms)")
            if checkpointer is not None and step % ckpt_every == 0:
                checkpointer.save(state, step)
        except SimulatedFailure as e:
            restarts += 1
            log(f"[trainer] {e} — recovering (restart {restarts})")
            if restarts > max_restarts:
                raise
            if checkpointer is not None:
                checkpointer.wait()
            if ckpt_dir is not None and ckpt_lib.available_steps(ckpt_dir):
                template = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    jax.eval_shape(fresh_state))
                sh = (state_shardings(model, mesh, zero1=zero1,
                                      grad_compress=grad_compress)
                      if mesh is not None else None)
                state, step0 = ckpt_lib.restore(ckpt_dir, template,
                                                shardings=sh)
                data_iter.load_state_dict({"step": step0})
                step = step0
                log(f"[trainer] restored step {step0}")
            else:
                state = fresh_state()
                data_iter.load_state_dict({"step": 0})
                step = 0
                log("[trainer] no checkpoint — restarted from scratch")

    if checkpointer is not None:
        checkpointer.save(state, step)
        checkpointer.wait()
    return TrainLoopResult(state, losses,
                           restarts,
                           watchdog.events if watchdog else [],
                           policies=pinned_policies)
