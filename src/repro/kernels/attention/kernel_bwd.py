"""Flash-attention backward Pallas kernels (paper Fig. 8 / §4.3), TPU-adapted.

The paper's attention-backward is its most register-pressured kernel, using
mixed MFMA shapes, row- *and* column-layout shared-memory reads and pinned
AGPR tiles (Tab. 1). The TPU instantiation splits the work the standard
flash-bwd way — a dq pass and a dk/dv pass — with pinned fp32 VMEM scratch
accumulators playing the role of the pinned register tiles, and the Pallas
pipeline providing the compute/memory alternation.

Block sizes come from a :class:`~repro.core.policy.KernelPolicy`
(``attention_bwd`` kind — its scratch accounting covers the dk+dv
accumulator pair, so a legal bwd policy may be smaller than the fwd one).
Traversal stays row-major: both passes accumulate over a full inner sweep
per output block, so the consecutive-revisit DMA model shows no gain from
reordering the outer dimension (DESIGN.md §5).

GQA: dk/dv are computed per *query* head and the (Hkv, group) reduction is
done by the caller (ops.py) — same strategy as the paper's 1.8-2.3x GQA-bwd
kernel, which parallelizes over query heads.

Epilogue chains (DESIGN.md §12) transpose under the attention saved-preact
convention: the only residuals are (out, lse). The softcap stage recomputes
the raw logits from the streamed q/k tiles, forms p from the *capped*
logits, and modulates ds by ``1 - tanh²(s/cap)`` in-kernel. A sink stage
needs nothing here — the fwd folded its mass into lse, so ``p = exp(s-lse)``
rows already sum to < 1 and ``ds = p·(dp - delta)`` is unchanged; dsink is
a jnp reduction in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import tiles
from repro.core.policy import (KernelPolicy, legacy_attention_blocks,
                               resolve_policy)

from .epilogue import ATTN_EPILOGUE_NONE, AttnEpilogue

MASK_VALUE = -1e30


def _p_and_dsfactor(s_raw, lse, epilogue, q_start, kv_start, causal, window):
    """(p, ds_factor) from the raw scaled logits under the epilogue chain.

    p is formed from the *capped* logits (matching the fwd); ds_factor is
    the softcap grad ``1 - tanh²(s/cap)`` (None for the identity chain).
    """
    s = epilogue.apply_logits(s_raw)
    p = _mask_and_p(s, lse, q_start, kv_start, causal, window)
    return p, epilogue.grad_factor(s_raw)


def _mask_and_p(s, lse, q_start, kv_start, causal, window):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    p = jnp.exp(s - lse)
    return jnp.where(mask, p, 0.0)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, nkv: int, block_q: int, block_kv: int,
               scale: float, causal: bool, window: int | None,
               epilogue: AttnEpilogue):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start, kv_start = iq * block_q, ik * block_kv
    run = True
    if causal:
        run = kv_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, q_start - (kv_start + block_kv - 1) < window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p, ds_factor = _p_and_dsfactor(s, lse, epilogue, q_start, kv_start,
                                       causal, window)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if ds_factor is not None:
            ds = ds * ds_factor
        ds = ds * scale
        acc_ref[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32)

    @pl.when(ik == nkv - 1)
    def _store():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, nq: int, block_q: int,
                block_kv: int, scale: float, causal: bool,
                window: int | None, epilogue: AttnEpilogue):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, kv_start = iq * block_q, ik * block_kv
    run = True
    if causal:
        run = kv_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, q_start - (kv_start + block_kv - 1) < window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p, ds_factor = _p_and_dsfactor(s, lse, epilogue, q_start, kv_start,
                                       causal, window)
        # dv += p^T @ do   (column-layout read in the paper; transposed dot here)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if ds_factor is not None:
            ds = ds * ds_factor
        ds = ds * scale
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _store():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("policy", "causal", "window", "logit_scale", "epilogue",
                     "interpret"),
)
def _flash_bwd(q, k, v, out, lse, do, *, policy: KernelPolicy,
               causal: bool, window: int | None,
               logit_scale: float | None, epilogue: AttnEpilogue,
               interpret: bool):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = h // hkv
    block_q = min(policy.block_q, sq)
    block_kv = min(policy.block_kv, skv)
    nq, nkv = sq // block_q, skv // block_kv
    scale = logit_scale if logit_scale is not None else d ** -0.5
    # ragged when the problem dims themselves are unaligned (see kernel_fwd)
    ragged_q = tiles.shape_ragged(sq, d, q.dtype)
    ragged_kv = tiles.shape_ragged(skv, d, k.dtype)

    policy.check()  # budget covers the larger of the two passes' scratch

    # delta = rowsum(dO * O): cheap, memory-bound; jnp preprocess (as in FA2/3)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)
    # lse and delta stream as (block_q, 1) columns (see kernel_fwd)
    lse = lse.reshape(b, h, sq, 1)

    def tile(shape, index_map, dtype, *, ragged):
        return tiles.block_spec(shape, index_map, dtype,
                                allow_ragged_minor=ragged)

    q_spec = tile((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0),
                  q.dtype, ragged=ragged_q)
    kv_spec = tile((1, 1, block_kv, d),
                   lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0),
                   k.dtype, ragged=ragged_kv)
    vec_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b_, h_, iq, ik: (b_, h_, iq, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nkv=nkv, block_q=block_q,
                          block_kv=block_kv, scale=scale, causal=causal,
                          window=window, epilogue=epilogue),
        grid=(b, h, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, vec_spec, vec_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=tiles.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv pass: grid transposed (kv outer, q inner), per query head.
    q_spec2 = tile((1, 1, block_q, d), lambda b_, h_, ik, iq: (b_, h_, iq, 0),
                   q.dtype, ragged=ragged_q)
    kv_spec2 = tile((1, 1, block_kv, d),
                    lambda b_, h_, ik, iq, g=group: (b_, h_ // g, ik, 0),
                    k.dtype, ragged=ragged_kv)
    kv_out_spec = tile((1, 1, block_kv, d),
                       lambda b_, h_, ik, iq: (b_, h_, ik, 0), k.dtype,
                       ragged=ragged_kv)
    vec_spec2 = pl.BlockSpec((1, 1, block_q, 1),
                             lambda b_, h_, ik, iq: (b_, h_, iq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, block_q=block_q,
                          block_kv=block_kv, scale=scale, causal=causal,
                          window=window, epilogue=epilogue),
        grid=(b, h, nkv, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, vec_spec2, vec_spec2],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, skv, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, skv, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        compiler_params=tiles.compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def flash_attention_bwd(q, k, v, out, lse, do, *,
                        policy: KernelPolicy | None = None,
                        causal: bool = False, window: int | None = None,
                        block_q: int | None = None,
                        block_kv: int | None = None,
                        logit_scale: float | None = None,
                        epilogue: AttnEpilogue | None = None,
                        interpret: bool = True):
    """Returns (dq, dk, dv) with dk/dv per *query* head: (B, H, Skv, D).

    ``epilogue``: the attention chain to transpose (saved-preact convention,
    see the module docstring); defaults to the policy's own epilogue field.
    """
    if policy is None:
        b, h, sq, d = q.shape
        skv = k.shape[2]
        policy = resolve_policy(
            "attention_bwd", (b, h, sq, skv, d), q.dtype, causal=causal,
            legacy_blocks=legacy_attention_blocks(block_q, block_kv, sq,
                                                  skv, d),
            warn_what="flash_attention_bwd")
    if epilogue is None:
        epilogue = (policy.epilogue if policy.epilogue is not None
                    else ATTN_EPILOGUE_NONE)
    if obs.enabled():
        from repro.core import autotune
        b, h, sq, d = q.shape
        skv = k.shape[2]
        sig = autotune.OpSignature("attention_bwd", (b, h, sq, skv, d),
                                   str(q.dtype), causal=causal,
                                   epilogue=policy.epilogue)
        obs.launch("attention_bwd",
                   variant="causal" if causal else "",
                   grid=(b, h, max(1, sq // policy.block_q)),
                   policy=policy, chain=str(epilogue.describe()),
                   dma_bytes=autotune.score_policy(sig, policy).dma_bytes,
                   flops=int(10 * b * h * sq * skv * d
                             * (0.5 if causal else 1.0)))
    return _flash_bwd(q, k, v, out, lse, do, policy=policy, causal=causal,
                      window=window, logit_scale=logit_scale,
                      epilogue=epilogue, interpret=interpret)
