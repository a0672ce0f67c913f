"""Flash-attention forward Pallas kernel (paper §4.2, listing E.3), TPU-adapted.

The paper's 8-wave ping-pong attention kernel alternates compute clusters
(MFMA + online-softmax vector ops) with load clusters (K/V tile prefetch).
On TPU the same alternation is the Pallas grid pipeline: iteration ik's
QK^T/PV MXU work overlaps iteration ik+1's K/V DMA. Online softmax state
(m, l, acc) lives in pinned fp32 VMEM scratch (the paper pins AGPRs).

Block sizes AND traversal order come from a
:class:`~repro.core.policy.KernelPolicy`: the (head, q-block) pair is fused
into one grid dimension and remapped by the policy's SwizzleConfig (the same
Algorithm-1 permutation the GEMM uses), so e.g. short-KV shapes can run
same-head q-blocks back-to-back and hit the Pallas K/V revisit fast path.
ROW_MAJOR reproduces the classic (b, h, iq, ik) traversal exactly.

Supports MHA and GQA (kv-head indexing in the BlockSpec index_map), causal
masking, and sliding-window masking (Mixtral/RecurrentGemma local attention).

The kernel also hosts the attention epilogue chain (DESIGN.md §12): an
:class:`~repro.kernels.attention.epilogue.AttnEpilogue` places the gemma2
logit soft cap inside the online-softmax loop (on the scaled logits, before
masking) and the attention-sink LSE combine at the output store, so neither
stage round-trips the score matrix or the output through HBM.
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
LANES = 128


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, nq: int, nkv: int, n_heads: int,
                block_q: int, block_kv: int, scale: float, causal: bool,
                window: int | None, swizzle, epilogue: AttnEpilogue):
    if epilogue.sink:
        sink_ref, o_ref, l_ref, acc_ref, m_ref, s_ref = refs
    else:
        o_ref, l_ref, acc_ref, m_ref, s_ref = refs
        sink_ref = None
    hq = pl.program_id(1)
    ik = pl.program_id(2)
    _, iq = swizzle.remap(hq, n_heads, nq)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        s_ref[...] = jnp.zeros_like(s_ref)

    q_start = iq * block_q
    kv_start = ik * block_kv

    # Skip kv blocks that are fully masked for every query row of this block.
    run = True
    if causal:
        run = kv_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, q_start - (kv_start + block_kv - 1) < window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # in-loop epilogue stage: tanh soft cap on the scaled logits,
        # pre-mask (identity when the chain has no cap)
        s = epilogue.apply_logits(s)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = kv_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_ref[:, :1]
        l_prev = s_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        s_ref[...] = jnp.broadcast_to(l_new, s_ref.shape)

    @pl.when(ik == nkv - 1)
    def _store():
        # store epilogue: the sink (if any) joins the LSE combine here —
        # epilogue.finalize re-anchors the running max at max(m, sink)
        # before forming the denominator (DESIGN.md §12)
        l = s_ref[:, :1]
        m = m_ref[:, :1]
        sink = sink_ref[...] if sink_ref is not None else None  # (1, 1)
        out, lse = epilogue.finalize(acc_ref[...], m, l, sink=sink)
        o_ref[0, 0] = out.astype(o_ref.dtype)
        # logsumexp residual for the backward pass (includes the sink mass,
        # which is what makes the saved-preact convention hold: the bwd
        # kernels need no sink operand at all)
        l_ref[0, 0] = lse


@functools.partial(
    jax.jit,
    static_argnames=("policy", "causal", "window", "logit_scale", "epilogue",
                     "interpret"),
)
def _flash_fwd(q, k, v, sinks, *, policy: KernelPolicy, causal: bool,
               window: int | None, logit_scale: float | None,
               epilogue: AttnEpilogue, interpret: bool):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    block_q = min(policy.block_q, sq)
    block_kv = min(policy.block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0, (sq, skv, block_q, block_kv)
    nq, nkv = sq // block_q, skv // block_kv
    scale = logit_scale if logit_scale is not None else d ** -0.5
    swizzle = policy.swizzle
    # ragged when the problem dims themselves are unaligned (head_dim 64
    # tiles — paper Fig. 7 — or short/odd sequences): Pallas pads those.
    ragged_q = tiles.shape_ragged(sq, d, q.dtype)
    ragged_kv = tiles.shape_ragged(skv, d, k.dtype)

    policy.check()  # Tab. 2 feasibility at the policy's pipeline depth

    def hq_coords(i):
        """Fused (head, q-block) grid index -> (head, q-block) via Algorithm 1."""
        return swizzle.remap(i, h, nq)

    def q_map(b_, i, ik):
        hh, iq = hq_coords(i)
        return (b_, hh, iq, 0)

    def kv_map(b_, i, ik):
        hh, _ = hq_coords(i)
        return (b_, hh // group, ik, 0)

    def lse_map(b_, i, ik):
        hh, iq = hq_coords(i)
        return (b_, hh, iq, 0)

    kernel = functools.partial(
        _fwd_kernel, nq=nq, nkv=nkv, n_heads=h, block_q=block_q,
        block_kv=block_kv, scale=scale, causal=causal, window=window,
        swizzle=swizzle, epilogue=epilogue)

    in_specs = [
        tiles.block_spec((1, 1, block_q, d), q_map, q.dtype,
                         allow_ragged_minor=ragged_q),
        tiles.block_spec((1, 1, block_kv, d), kv_map, k.dtype,
                         allow_ragged_minor=ragged_kv),
        tiles.block_spec((1, 1, block_kv, d), kv_map, v.dtype,
                         allow_ragged_minor=ragged_kv),
    ]
    operands = [q, k, v]
    if epilogue.sink:
        assert sinks is not None, "sink epilogue needs a sinks operand"
        # one f32 scalar per head, streamed per (head, q-block) grid cell;
        # the (1, 1) trailing dims equal the array's, as the TPU lowering
        # requires of a block
        in_specs.append(pl.BlockSpec(
            (None, 1, 1), lambda b_, i, ik: (hq_coords(i)[0], 0, 0)))
        operands.append(
            jnp.asarray(sinks, jnp.float32).reshape(h, 1, 1))

    grid = (b, h * nq, nkv)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            tiles.block_spec((1, 1, block_q, d), q_map, q.dtype,
                             allow_ragged_minor=ragged_q),
            # lse as a (block_q, 1) column: a trailing dim of 1 equals the
            # array's, which the TPU lowering accepts for a block
            pl.BlockSpec((1, 1, block_q, 1), lse_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),      # acc (pinned, DESIGN §2)
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running sum l
        ],
        compiler_params=tiles.compiler_params(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out, lse[..., 0]


def flash_attention_fwd(q, k, v, *, policy: KernelPolicy | None = None,
                        causal: bool = False, window: int | None = None,
                        block_q: int | None = None,
                        block_kv: int | None = None,
                        logit_scale: float | None = None,
                        epilogue: AttnEpilogue | None = None,
                        sinks=None,
                        interpret: bool = True):
    """Returns (out, lse). q: (B,H,Sq,D), k/v: (B,Hkv,Skv,D).

    ``epilogue`` is the fused attention store chain (softcap/sink stages,
    DESIGN.md §12); ``sinks`` is the (H,) f32 operand the sink stage
    streams. When the chain is omitted, the policy's own epilogue field
    applies (the autotuner attaches it there).

    Explicit ``block_q``/``block_kv`` is the deprecated pre-policy surface
    (builds an equivalent explicit row-major policy); with neither a policy
    nor blocks, the autotuner resolves one per shape-bucket.
    """
    if policy is None:
        b, h, sq, d = q.shape
        skv = k.shape[2]
        policy = resolve_policy(
            "attention_fwd", (b, h, sq, skv, d), q.dtype, causal=causal,
            legacy_blocks=legacy_attention_blocks(block_q, block_kv, sq,
                                                  skv, d),
            warn_what="flash_attention_fwd")
    if epilogue is None:
        epilogue = (policy.epilogue if policy.epilogue is not None
                    else ATTN_EPILOGUE_NONE)
    if obs.enabled():
        from repro.core import autotune
        b, h, sq, d = q.shape
        skv = k.shape[2]
        sig = autotune.OpSignature("attention_fwd", (b, h, sq, skv, d),
                                   str(q.dtype), causal=causal,
                                   epilogue=policy.epilogue)
        obs.launch("attention_fwd",
                   variant="windowed" if window else
                   ("causal" if causal else ""),
                   grid=(b, h, max(1, sq // policy.block_q)),
                   policy=policy, chain=str(epilogue.describe()),
                   dma_bytes=autotune.score_policy(sig, policy).dma_bytes,
                   flops=int(4 * b * h * sq * skv * d
                             * (0.5 if causal else 1.0)))
    return _flash_fwd(q, k, v, sinks, policy=policy, causal=causal,
                      window=window, logit_scale=logit_scale,
                      epilogue=epilogue, interpret=interpret)
