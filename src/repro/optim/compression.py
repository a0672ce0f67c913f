"""int8 error-feedback gradient compression (distributed-optimization trick).

On a real fleet the slow hop is the cross-pod data-parallel all-reduce; int8
quantization cuts its bytes 4x. Error feedback (Seide et al. / EF-SGD) keeps
the quantization bias from accumulating: the residual of each step's
quantization is added back into the next step's gradient.

Two layers here:
  * :func:`ef_compress` — pure numerics (quantize → dequantize + EF state),
    applied to gradients before the optimizer. This is exactly what the
    receiving end of a compressed all-reduce sees, so convergence behavior is
    faithfully exercised even on one process.
  * :func:`compressed_psum` — the shard_map collective: quantize per-shard,
    psum int32-accumulated int8 payloads, dequantize. Used by tests on the
    8-device host platform and by the launcher on a real mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _quant(g: jax.Array):
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def ef_compress(grads, ef_state):
    """Quantize+dequantize each leaf with error feedback.

    Returns (dequantized grads, new ef_state). ef_state is a tree of fp32
    residuals with the same structure as grads (zeros initially).
    """
    def leaf(g, e):
        gf = g.astype(jnp.float32) + e
        q, scale = _quant(gf)
        deq = q.astype(jnp.float32) * scale
        return deq.astype(g.dtype), gf - deq

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(ef_state)
    out = [leaf(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in out]),
            tdef.unflatten([o[1] for o in out]))


def ef_init(grads_or_params):
    return jax.tree.map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_or_params)


def compressed_psum(x: jax.Array, mesh, axis: str = "data") -> jax.Array:
    """int8-payload psum over ``axis`` of a replicated-shape array.

    Each participant quantizes its local contribution; int8 payloads are
    summed in int32 (exact), then dequantized with the max scale. 4x fewer
    bytes on the wire than an f32 ring all-reduce.
    """
    @functools.partial(shard_map, mesh=mesh,
                       in_specs=P(*([None] * x.ndim)),
                       out_specs=P(*([None] * x.ndim)), check_vma=False)
    def inner(v):
        q, scale = _quant(v.astype(jnp.float32))
        # all participants must dequantize with a common scale: use the max
        scale = jax.lax.pmax(scale, axis)
        q = jnp.clip(jnp.round(v.astype(jnp.float32) / scale), -127, 127
                     ).astype(jnp.int8)
        total = jax.lax.psum(q.astype(jnp.int32), axis)
        return total.astype(jnp.float32) * scale

    return inner(x)
