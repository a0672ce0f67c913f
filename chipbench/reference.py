"""The plain reference: the model the configuration files describe, written
from the published description in float32 ``jax.numpy`` at the highest
matmul precision, with no kernels, cache or batching. It imports nothing of
the program. The configuration's family (``families/<family>.py``) gives
the architecture (``hidden``, ``logits``) from the pieces here; the
readings built on them (``served_gaps``, ``loss``) and the optimizer are
the same for every family. A family runs its layers one at a time under a
scan, each upcast from the weights as held only inside its own step, so
the reference fits beside nothing else on the chip.

RoPE rotates the two halves of each head (x1, x2) -> (x1 c - x2 s,
x2 c + x1 s) with frequencies theta^(-i / (hd/2)); attention is causal
softmax(q k^T / sqrt(hd)) v, keys and values shared by groups of heads.

``quant="fp8"`` is the control: every matmul input rounded to float8 e4m3
(weights per output column, activations per row) and, in a backward pass,
every gradient reaching a matmul input rounded to float8 e5m2 (scaled the
same way), the products still in float32: fp8 training as it is done. It
stands for the nearest precision below the bf16 the configurations
compute in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .model_spec import ModelSpec, family

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
_FWD = (jnp.float8_e4m3fn, 448.0)
_BWD = (jnp.float8_e5m2, 57344.0)


def _round(x, axis, fmt):
    dtype, top = fmt
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fake_quant(x, axis):
    return _round(x, axis, _FWD)


def _fq_fwd(x, axis):
    return _round(x, axis, _FWD), None


def _fq_bwd(axis, _, g):
    return (_round(g, axis, _BWD),)


_fake_quant.defvjp(_fq_fwd, _fq_bwd)


def mm(a, b, quant=None):
    """a (..., k) @ b (k, n) in float32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant == "fp8":
        a, b = _fake_quant(a, -1), _fake_quant(b, -2)
    return jnp.matmul(a, b, precision=HI)


def rmsnorm(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        g.astype(jnp.float32)


def rope(x, positions, theta):
    """x (S, n, hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, quant=None):
    """Causal GQA for one sequence: q (S, H, hd), k/v (S, Hkv, hd) ->
    (S, H * hd), queries in blocks of Q_BLOCK rows."""
    s, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if quant == "fp8":
        q, k, v = (_fake_quant(t, -1) for t in (q, k, v))
    blk = min(Q_BLOCK, s)
    qb = q.reshape(s // blk, blk, h, hd)

    @jax.checkpoint
    def one(args):
        i, qi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) / jnp.sqrt(
            jnp.float32(hd))
        rows = i * blk + jnp.arange(blk)
        sc = jnp.where(jnp.arange(s)[None, None, :] <= rows[None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quant == "fp8":
            p = _fake_quant(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(one, (jnp.arange(s // blk), qb))
    return out.reshape(s, h * hd)


def hidden(spec: ModelSpec, w: dict, tokens, quant=None, remat=False):
    """Final-normed hidden states of one sequence: tokens (S,) -> (S, d)."""
    return family(spec).hidden(spec, w, tokens, quant, remat)


def head(spec: ModelSpec, w: dict):
    return w["embed"].T if spec.tied else w["lm_head"]


def logits(spec: ModelSpec, w: dict, h, quant=None):
    return family(spec).logits(spec, w, h, quant)


@functools.partial(jax.jit, static_argnums=(0, 5))
def served_gaps(spec: ModelSpec, w: dict, tokens, rows, served, quant=None):
    """For one sequence padded to a fixed length: at each row r of ``rows``
    (the position whose next token was served as ``served``), how far the
    reference's logit of the served token lies below its best, and the
    gap of the token ``quant``'s logits rank first when ``quant`` is set.
    Returns (gap of served, gap of quant's first choice)."""
    h = hidden(spec, w, tokens)
    hr = h[rows]
    ref = logits(spec, w, hr)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if quant is None:
        return gap, jnp.zeros_like(gap)
    hq = hidden(spec, w, tokens, quant)[rows]
    pick = logits(spec, w, hq, quant).argmax(-1)
    return gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def loss(spec: ModelSpec, w: dict, batch: dict, quant=None,
         chunk: int = 512):
    """Mean next-token cross entropy over the unmasked positions of a
    batch, the vocabulary's logits made ``chunk`` rows at a time."""
    def row_nll(tokens, targets, mask):
        h = hidden(spec, w, tokens, quant, remat=True)
        c = min(chunk, h.shape[0])
        n = h.shape[0] // c

        @jax.checkpoint
        def part(args):
            hc, tc, mc = args
            lg = logits(spec, w, hc, quant)
            nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, tc[:, None], -1)[:, 0]
            return jnp.sum(nll * mc)

        parts = jax.lax.map(part, (h.reshape(n, c, -1),
                                   targets.reshape(n, c),
                                   mask.reshape(n, c)))
        return parts.sum()

    total = 0.0
    for b in range(batch["inputs"].shape[0]):
        total = total + row_nll(batch["inputs"][b], batch["targets"][b],
                                batch["loss_mask"][b])
    return total / jnp.maximum(batch["loss_mask"].sum(), 1.0)


def adamw_step(opt: dict, w: dict, state: dict, grads: dict):
    """AdamW (Loshchilov & Hutter) with global-norm clipping; the decay is
    scaled by the learning rate. Returns (new weights, new state)."""
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    t = state["t"] + 1
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, state["v"],
                     grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    lr = opt["learning_rate"]

    def upd(p, m_, v_):
        step = m_ / c1 / (jnp.sqrt(v_ / c2) + opt["eps"])
        return p - lr * (step + opt["weight_decay"] * p)

    return (jax.tree.map(upd, w, m, v), {"m": m, "v": v, "t": t},
            grads)
