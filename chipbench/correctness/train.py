"""Training steps against the reference. The program's numbers are read
from the state the trainer's own step receives (``StepProbe``): after step
1 the first gradient as AdamW gets it (its first moment / (1 - b1), copied
to the host), after step 3 each leaf's change from the initial weights.
The reference runs the same three steps on the same rows in float32 at the
highest precision, from weights it draws itself from the seed.

Numbers compared, each by the worst leaf. Leaves whose reference gradient
is under a thousandth of the median leaf's are left out of the update's
(round-off alone moves them under AdamW) and of the gradient's direction.
  loss_gap         max over steps 1-3 of |loss - reference loss| (nats)
  grad_norm_gap    | |g| - |g_ref| |, first gradient, against the larger
                   of the reference's norm of that leaf and of the median
                   leaf
  grad_rel_l2      |g - g_ref| / |g_ref|, first gradient, against the
                   leaf's own norm: the direction as well as the size, so
                   a fault confined to a small leaf (a norm's scale) shows
                   (the norms alone do not tell the fp8 control from the
                   program, see PERF.md)
  update_norm_gap  | |p3 - p0| - |p3_ref - p0| |, against the larger of
                   the reference's change of that leaf and of the median
                   leaf
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .. import reference, system, weights

STEPS = 3
NUMBERS = ("loss_gap", "grad_norm_gap", "grad_rel_l2", "update_norm_gap")


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _change_norm(spec, name, leaf, dtype, key):
    p0 = weights.leaf(spec, name, key, jnp.dtype(dtype))
    return jnp.linalg.norm(leaf[:p0.shape[0]].astype(jnp.float32)
                           - p0.astype(jnp.float32))


def change_norms(spec, params: dict, seed: int) -> dict:
    """|p - p0| per leaf, p0 drawn again from the seed leaf by leaf."""
    key = weights.seed_key(seed)
    return {k: _change_norm(spec, k, v, str(v.dtype), key)
            for k, v in params.items()}


class StepProbe:
    """Wraps the trainer's step factory; the step it returns is the
    trainer's own, called unchanged, after reading the state it receives
    on calls 2 and STEPS + 1."""

    def __init__(self, spec, seed: int, b1: float):
        self.spec, self.seed, self.b1 = spec, seed, b1
        self.calls = 0
        self.grad = self.change = None

    def wrap(self, make):
        def make_probed(*a, **k):
            step = make(*a, **k)

            def probed(state, batch):
                self.calls += 1
                if self.calls == 2:
                    m = system.canonical(self.spec, state["opt"]["m"])
                    self.grad = {n: np.asarray(v, np.float32)
                                 / (1 - self.b1) for n, v in m.items()}
                elif self.calls == STEPS + 1:
                    self.change = change_norms(
                        self.spec,
                        system.canonical(self.spec, state["params"]),
                        self.seed)
                return step(state, batch)
            return probed
        return make_probed

    def readings(self) -> dict:
        return {"grad_vec": self.grad,
                "change": {k: float(v) for k, v in self.change.items()}}


@functools.partial(jax.jit, static_argnums=(0, 1, 5), donate_argnums=(2, 3))
def _ref_step(spec, opt_items, w, state, batch, quant=None):
    loss, grads = jax.value_and_grad(
        lambda w_: reference.loss(spec, w_, batch, quant))(w)
    w, state, clipped = reference.adamw_step(dict(opt_items), w, state,
                                             grads)
    return w, state, loss, clipped


@jax.jit
def _diff_norm(a, b):
    return jnp.linalg.norm(a[:b.shape[0]] - b)


def reference_readings(spec, opt: dict, batches: list, seed: int,
                       quant=None) -> dict:
    """Losses of steps 1-3, the first (clipped) gradient on the host, and
    each leaf's change after the three steps."""
    w = weights.make(spec, seed, jnp.float32)
    state = {"m": jax.tree.map(jnp.zeros_like, w),
             "v": jax.tree.map(jnp.zeros_like, w),
             "t": jnp.zeros((), jnp.float32)}
    items = tuple(sorted(opt.items()))
    losses, grad = [], None
    for k in range(STEPS):
        b = {n: jnp.asarray(v) for n, v in batches[k].items()}
        w, state, loss, g = _ref_step(spec, items, w, state, b, quant)
        losses.append(loss)
        if k == 0:
            grad = {n: np.asarray(v) for n, v in g.items()}
        del g
    del state
    change = change_norms(spec, w, seed)
    return {"losses": [float(x) for x in losses], "grad_vec": grad,
            "change": {k: float(v) for k, v in change.items()}}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers of NUMBERS for ``prog`` against ``ref`` (both as
    returned above, ``prog`` with its first three losses), with the leaf
    that set each and the leaves left out of the update's."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                              ref["losses"]))
    gp, gr = prog["grad_vec"], ref["grad_vec"]
    ref_norm = {k: float(np.linalg.norm(v)) for k, v in gr.items()}
    med = float(np.median(list(ref_norm.values())))
    kept = sorted(k for k in gr if ref_norm[k] >= 1e-3 * med)
    prog_norm = {k: float(np.linalg.norm(gp[k][:gr[k].shape[0]]))
                 for k in gr}
    diff = {k: float(_diff_norm(jnp.asarray(gp[k]), jnp.asarray(gr[k])))
            for k in gr}

    def worst(gap, base, leaves, own=False):
        b = 0.0 if own else float(np.median([base[k] for k in leaves]))
        rel = {k: gap[k] / max(base[k], b) for k in leaves}
        k = max(rel, key=rel.get)
        return rel[k], k

    out = {"loss_gap": loss_gap}
    out["grad_norm_gap"], out["worst_grad_leaf"] = worst(
        {k: abs(prog_norm[k] - ref_norm[k]) for k in gr}, ref_norm,
        sorted(gr))
    out["grad_rel_l2"], out["worst_grad_dir_leaf"] = worst(
        diff, ref_norm, kept, own=True)
    rc = ref["change"]
    out["update_norm_gap"], out["worst_update_leaf"] = worst(
        {k: abs(prog["change"][k] - rc[k]) for k in kept}, rc, kept)
    out["left_out"] = sorted(set(gr) - set(kept))
    return out
