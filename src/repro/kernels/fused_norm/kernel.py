"""Fused dropout + residual + layernorm Pallas kernel (paper Fig. 9/22).

One pass over the activations: generate the dropout mask *in-kernel* from a
counter-based hash (no HBM mask traffic — the TPU-portable equivalent of the
paper's in-register dropout_mask), scale, add the residual, emit the residual
stream, then layernorm in fp32. Memory-bound by construction: exactly
2 reads + 2 writes of (rows, d) plus the (d,) affine params.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import tiles
from repro.core.policy import KernelPolicy, resolve_policy


def _lowbias32(x: jax.Array) -> jax.Array:
    """Counter-based 32-bit mix (lowbias32); identical fn lives in ref.py."""
    x = x.astype(jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x7FEB352D)
    x ^= x >> 15
    x *= jnp.uint32(0x846CA68B)
    x ^= x >> 16
    return x


def dropout_keep_mask(seed: jax.Array, row0, shape, p: float) -> jax.Array:
    """Deterministic keep-mask for rows [row0, row0+shape[0]) — uniform >= p."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    idx = rows.astype(jnp.uint32) * jnp.uint32(shape[1]) + cols.astype(jnp.uint32)
    bits = _lowbias32(idx ^ _lowbias32(jnp.uint32(seed)))
    uniform = (bits >> jnp.uint32(8)).astype(jnp.float32) * (1.0 / (1 << 24))
    return uniform >= p


def _fused_kernel(seed_ref, x_ref, res_ref, w_ref, b_ref, o_ref, oresid_ref,
                  *, block_rows: int, dropout_p: float, eps: float):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    resid = res_ref[...].astype(jnp.float32)

    if dropout_p > 0.0:
        keep = dropout_keep_mask(seed_ref[0], i * block_rows, x.shape, dropout_p)
        x = jnp.where(keep, x * (1.0 / (1.0 - dropout_p)), 0.0)

    resid = resid + x
    oresid_ref[...] = resid.astype(oresid_ref.dtype)

    mean = jnp.mean(resid, axis=1, keepdims=True)
    centered = resid - mean
    var = jnp.mean(centered * centered, axis=1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = (centered * inv * w + b).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("dropout_p", "eps", "policy", "interpret"),
)
def _fused(x, residual, weight, bias, seed, *, policy: KernelPolicy,
           dropout_p: float, eps: float, interpret: bool):
    rows, d = x.shape
    block_rows = min(policy.block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    seed_arr = jnp.asarray([seed], jnp.int32) if jnp.ndim(seed) == 0 else seed

    row_spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    out, new_resid = pl.pallas_call(
        functools.partial(_fused_kernel, block_rows=block_rows,
                          dropout_p=dropout_p, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  row_spec, row_spec, vec_spec, vec_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, d), x.dtype),
                   jax.ShapeDtypeStruct((rows, d), x.dtype)],
        compiler_params=tiles.compiler_params(),
        interpret=interpret,
    )(seed_arr, x, residual, weight.reshape(1, d), bias.reshape(1, d))
    return out, new_resid


def fused_dropout_residual_layernorm(x, residual, weight, bias, seed,
                                     *, policy: KernelPolicy | None = None,
                                     dropout_p: float = 0.0,
                                     eps: float = 1e-5,
                                     block_rows: int | None = None,
                                     interpret: bool = True):
    """x, residual: (rows, d); weight/bias: (d,). Returns (normed, new_residual).

    Explicit ``block_rows`` is the deprecated pre-policy surface; with
    neither a policy nor a block, the autotuner selects the row block.
    """
    rows, d = x.shape
    if policy is None:
        legacy = (None if block_rows is None
                  else dict(block_rows=min(block_rows, rows), d=d))
        policy = resolve_policy("fused_norm", (rows, d), x.dtype,
                                legacy_blocks=legacy, warn_what="fused_norm")
    if obs.enabled():
        from repro.core import perf_model as pm
        obs.launch("fused_norm",
                   grid=(max(1, rows // min(policy.block_rows, rows)),),
                   policy=policy,
                   dma_bytes=pm.dropout_residual_ln_traffic(
                       rows, d, dtype_bytes=jnp.dtype(x.dtype).itemsize),
                   flops=10 * rows * d)
    return _fused(x, residual, weight, bias, seed, policy=policy,
                  dropout_p=dropout_p, eps=eps, interpret=interpret)
