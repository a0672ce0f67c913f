"""Rotary positional embedding Pallas kernel (paper Fig. 9).

Memory-bound elementwise rotate: out = x*cos + rotate_half(x)*sin with the
(S, D) sin/cos tables streamed once per sequence block and reused across the
(batch, head) grid dims — the same reuse the paper's RoPE kernel gets from
keeping the tables resident.

sin/cos are passed *duplicated across halves* (shape (S, D)) so the kernel's
minor dim stays lane-aligned (128) — the TPU analogue of the paper's "pick
layouts that keep every access pattern conflict-free" rule.

The sequence block comes from a 1-D :class:`~repro.core.policy.KernelPolicy`
(``rope`` kind; block_m = block_s, block_k = head_dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import obs
from repro.core import tiles
from repro.core.policy import KernelPolicy, resolve_policy


def _rope_kernel(x_ref, sin_ref, cos_ref, o_ref):
    x = x_ref[0, 0].astype(jnp.float32)
    sin = sin_ref[...].astype(jnp.float32)
    cos = cos_ref[...].astype(jnp.float32)
    d = x.shape[-1]
    x1 = x[:, : d // 2]
    x2 = x[:, d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    o_ref[0, 0] = (x * cos + rotated * sin).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("policy", "interpret"))
def _rope(x, sin, cos, *, policy: KernelPolicy, interpret: bool):
    b, h, s, d = x.shape
    assert sin.shape == (s, d) and cos.shape == (s, d), (sin.shape, x.shape)
    block_s = min(policy.block_rows, s)
    assert s % block_s == 0

    x_spec = tiles.block_spec((1, 1, block_s, d),
                              lambda b_, h_, i: (b_, h_, i, 0), x.dtype,
                              allow_ragged_minor=tiles.shape_ragged(
                                  s, d, x.dtype))
    t_spec = tiles.block_spec((block_s, d), lambda b_, h_, i: (i, 0),
                              sin.dtype,
                              allow_ragged_minor=tiles.shape_ragged(
                                  s, d, sin.dtype))
    return pl.pallas_call(
        _rope_kernel,
        grid=(b, h, s // block_s),
        in_specs=[x_spec, t_spec, t_spec],
        out_specs=x_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=tiles.compiler_params(),
        interpret=interpret,
    )(x, sin, cos)


def rope_pallas(x, sin, cos, *, policy: KernelPolicy | None = None,
                block_s: int | None = None, interpret: bool = True):
    """x: (B, H, S, D); sin/cos: (S, D) duplicated halves. Returns rotated x.

    Explicit ``block_s`` is the deprecated pre-policy surface; with neither
    a policy nor a block, the autotuner selects the sequence block.
    """
    if policy is None:
        b, h, s, d = x.shape
        legacy = (None if block_s is None
                  else dict(block_s=min(block_s, s), d=d))
        policy = resolve_policy("rope", (b, h, s, d), x.dtype,
                                legacy_blocks=legacy, warn_what="rope_pallas")
    if obs.enabled():
        from repro.core import perf_model as pm
        b, h, s, d = x.shape
        obs.launch("rope",
                   grid=(b, h, max(1, s // min(policy.block_rows, s))),
                   policy=policy,
                   dma_bytes=pm.rope_traffic(b, h, s, d),
                   flops=6 * b * h * s * d)
    return _rope(x, sin, cos, policy=policy, interpret=interpret)
