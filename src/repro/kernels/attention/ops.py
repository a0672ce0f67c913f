"""Public attention op with custom VJP through the Pallas kernels.

``attention(q, k, v, causal=..., window=..., mode=...)``:
  * mode="reference"        — jnp softmax attention, jax autodiff (dry-run path)
  * mode="pallas_interpret" — flash fwd/bwd kernels, interpret=True
  * mode="pallas_tpu"       — same kernels lowered for TPU

Policy resolution order (DESIGN.md §5): explicit ``policy``/``bwd_policy`` >
legacy ``block_q``/``block_kv`` keywords (deprecation shim) > the analytic
autotuner, which resolves fwd and bwd policies independently (the bwd pass
has a larger scratch working set and may legally need smaller tiles).

Attention epilogue chains (DESIGN.md §12): ``softcap``/``sinks`` build an
:class:`~repro.kernels.attention.epilogue.AttnEpilogue` that rides the
resolved policy (and its autotune bucket). The fused-vs-unfused decision is
a real plan: ``autotune.select_fusion("attention", ...)`` scores the flash
chain against the eager score-matrix chain from modeled ``dma_bytes``, the
same protocol every GEMM-side fusion uses. The sink operand is a
*differentiable* input — ``_flash``'s VJP returns dsink alongside dq/dk/dv
(a jnp reduction over the saved (out, lse) residuals; the kernels never
see a sink gradient because the fwd folded the sink mass into lse).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import autotune, tiles
from repro.core.policy import (KernelPolicy, legacy_attention_blocks,
                               make_policy, resolve_policy)
from repro.kernels.modes import interpret_for
from .epilogue import AttnEpilogue
from .kernel_fwd import flash_attention_fwd
from .kernel_bwd import flash_attention_bwd
from .kernel_decode import (flash_decode, flash_decode_paged,
                            paged_heads_per_step, paged_vmem_bytes)
from .ref import attention_ref, attention_ref_chunked, decode_ref

# above this KV length, 'reference' mode switches to the chunked
# online-softmax scan so temps stay O(S·chunk) instead of O(S^2)
_CHUNKED_THRESHOLD = 2048
# tokens of pages one paged-decode grid step walks (a whole number of
# pages): enough that the fixed cost of a grid step is small beside the
# block's DMA, few enough that a slot's last block wastes little
_PAGED_BLOCK_TOKENS = 512


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, sinks, causal, window, policy, bwd_policy, logit_scale,
           epilogue, interpret):
    out, _ = flash_attention_fwd(
        q, k, v, policy=policy, causal=causal, window=window,
        logit_scale=logit_scale, epilogue=epilogue, sinks=sinks,
        interpret=interpret)
    return out


def _flash_fwd(q, k, v, sinks, causal, window, policy, bwd_policy,
               logit_scale, epilogue, interpret):
    out, lse = flash_attention_fwd(
        q, k, v, policy=policy, causal=causal, window=window,
        logit_scale=logit_scale, epilogue=epilogue, sinks=sinks,
        interpret=interpret)
    # saved-preact convention: (out, lse) are the only residuals — lse
    # already contains the sink mass, softcap recomputes in-kernel
    return out, (q, k, v, sinks, out, lse)


def _flash_bwd(causal, window, policy, bwd_policy, logit_scale, epilogue,
               interpret, res, do):
    q, k, v, sinks, out, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, do, policy=bwd_policy, causal=causal,
        window=window, logit_scale=logit_scale, epilogue=epilogue,
        interpret=interpret)
    h, hkv = q.shape[1], k.shape[1]
    if h != hkv:  # GQA: reduce per-query-head dk/dv over the group
        group = h // hkv
        b, _, skv, d = dk.shape
        dk = dk.reshape(b, hkv, group, skv, d).sum(axis=2)
        dv = dv.reshape(b, hkv, group, skv, d).sum(axis=2)
    dsinks = None
    if sinks is not None:
        dsinks = epilogue.operand_grads(do, out, lse, sinks=sinks)["sinks"]
        dsinks = dsinks.astype(sinks.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), dsinks


_flash.defvjp(_flash_fwd, _flash_bwd)


def resolve_attention_policies(q_shape, kv_shape, dtype, *,
                               causal: bool = False,
                               epilogue: AttnEpilogue | None = None) -> tuple:
    """(fwd, bwd) tuned policies for a (B,H,Sq,D) x (B,Hkv,Skv,D) launch.

    A non-identity ``epilogue`` joins the autotune signature (its streamed
    operands count in the VMEM legality rule and its extra reads in the
    traffic score) and rides the returned policies' epilogue field.
    """
    b, h, sq, d = q_shape
    skv = kv_shape[2]
    sig = (b, h, sq, skv, d)
    ep = epilogue if epilogue is not None and not epilogue.is_identity else None
    fwd = autotune.select_policy("attention_fwd", sig, str(dtype),
                                 causal=causal, epilogue=ep)
    bwd = autotune.select_policy("attention_bwd", sig, str(dtype),
                                 causal=causal, epilogue=ep)
    return fwd, bwd


def attention(q, k, v, *, causal: bool = False, window: int | None = None,
              policy: KernelPolicy | None = None,
              bwd_policy: KernelPolicy | None = None,
              block_q: int | None = None, block_kv: int | None = None,
              logit_scale: float | None = None,
              softcap: float | None = None, sinks=None,
              mode: str = "pallas_interpret"):
    """Multi-/grouped-query flash attention. q:(B,H,S,D), k/v:(B,Hkv,S,D).

    ``softcap``: gemma2-style tanh logit cap (configs/base.py
    ``attn_logit_softcap``), applied inside the kernels' softmax loop.
    ``sinks``: optional (H,) per-head attention-sink logits (differentiable
    — grads flow to them like any other operand). Both stages form the
    fused :class:`AttnEpilogue` store chain; reference mode applies the
    identical math in jnp.
    """
    epilogue = AttnEpilogue(softcap=float(softcap) if softcap else 0.0,
                            sink=sinks is not None)
    interpret = interpret_for(mode)
    if mode == "reference":
        if k.shape[2] > _CHUNKED_THRESHOLD:
            return attention_ref_chunked(q, k, v, causal=causal,
                                         window=window,
                                         logit_scale=logit_scale,
                                         softcap=softcap, sinks=sinks)
        return attention_ref(q, k, v, causal=causal, window=window,
                             logit_scale=logit_scale, softcap=softcap,
                             sinks=sinks)
    if policy is None:
        b, h, sq, d = q.shape
        skv = k.shape[2]
        legacy = legacy_attention_blocks(block_q, block_kv, sq, skv, d)
        if legacy is not None:
            # legacy keyword surface -> explicit policy (deprecation shim)
            sig = (b, h, sq, skv, d)
            policy = resolve_policy("attention_fwd", sig, q.dtype,
                                    causal=causal, legacy_blocks=legacy,
                                    warn_what="attention")
            bwd_policy = bwd_policy or resolve_policy(
                "attention_bwd", sig, q.dtype, causal=causal,
                legacy_blocks=legacy, warn_what="attention")
        else:
            # plan decision: the flash chain vs the eager score-matrix
            # chain, from modeled dma_bytes — same protocol as the
            # mlp/qkv_rope plans (memoized per shape bucket)
            hkv = k.shape[1]
            plan = autotune.select_fusion(
                "attention", (b, h, hkv, sq, skv, d), str(q.dtype),
                causal=causal, softcap=bool(epilogue.softcap),
                sink=epilogue.sink)
            if plan["plan"] != "fused":
                # modeled traffic favors the eager chain (never at real
                # shapes — the flash chain strictly dominates — but the
                # plan, not the call site, owns that decision)
                obs.incr("fallback.eager.attention")
                return attention_ref(q, k, v, causal=causal, window=window,
                                     logit_scale=logit_scale,
                                     softcap=softcap, sinks=sinks)
            policy, auto_bwd = resolve_attention_policies(
                q.shape, k.shape, q.dtype, causal=causal, epilogue=epilogue)
            bwd_policy = bwd_policy or auto_bwd
    elif bwd_policy is None:
        _, bwd_policy = resolve_attention_policies(
            q.shape, k.shape, q.dtype, causal=causal, epilogue=epilogue)
    return _flash(q, k, v, sinks, causal, window, policy, bwd_policy,
                  logit_scale, epilogue, interpret)


# ---------------------------------------------------------------------------
# Decode path (q_len = 1): split-KV flash-decode + paged-attention variant.
# ---------------------------------------------------------------------------

def resolve_decode_policy(batch: int, kv_heads: int, group: int, kv_len: int,
                          head_dim: int, dtype, *,
                          page_size: int | None = None,
                          epilogue: AttnEpilogue | None = None,
                          q_tokens: int = 1) -> KernelPolicy:
    """The decode policy for a launch signature (DESIGN.md §5 / §8).

    Contiguous caches go through the autotuner (the split size is the one
    free axis of the bandwidth-dominated model). Paged caches split by
    blocks of whole pages: about ``_PAGED_BLOCK_TOKENS`` tokens of pages a
    grid step, capped at the table's ``kv_len // page_size`` pages and
    halved while the kernel's working set (``paged_vmem_bytes``: a tall
    ``q_tokens`` tile) overflows VMEM. The policy is built directly from
    the shape — deterministically, so an engine's pinned policy and the
    traced policy are the same object semantics as the autotuner's
    memoized path. A non-identity ``epilogue`` rides the policy for
    reporting (decode's sink stage lives in the jnp LSE combine, so it
    never affects decode VMEM legality).
    """
    ep = epilogue if epilogue is not None and not epilogue.is_identity else None
    if page_size is None:
        return autotune.select_policy(
            "attention_decode", (batch, kv_heads, group, kv_len, head_dim),
            str(dtype), epilogue=ep)
    ppb = max(1, min(_PAGED_BLOCK_TOKENS // page_size, kv_len // page_size))
    while ppb > 1 and paged_vmem_bytes(
            kv_heads=kv_heads, group=group, q_tokens=q_tokens,
            pages_per_block=ppb, page_size=page_size, head_dim=head_dim,
            dtype=dtype) > tiles.VMEM_BYTES:
        ppb //= 2
    # q tile rows = GQA group × verify tokens (q_tokens > 1 is the
    # speculative verify step or a prefill chunk — same paged split,
    # taller q tile)
    pol = make_policy("attention_decode", block_m=group * q_tokens,
                      block_n=ppb * page_size, block_k=head_dim,
                      in_dtype=str(jnp.dtype(dtype)),
                      name="paged" if q_tokens == 1 else f"paged_q{q_tokens}",
                      epilogue=ep)
    pol.check()
    return pol


def attention_decode(q, k, v, lengths, *, window: int | None = None,
                     policy: KernelPolicy | None = None,
                     logit_scale: float | None = None,
                     softcap: float | None = None, sinks=None,
                     mode: str = "pallas_interpret"):
    """Single-token decode attention over a contiguous (ring) KV cache.

    q: (B, H, 1, D) with H % Hkv == 0; k/v: (B, Hkv, S, D);
    ``lengths``: scalar or (B,) int32 — tokens written so far (ring
    semantics when lengths > S). ``softcap``/``sinks`` follow
    :func:`attention` (sinks is (H,), per query head). Returns
    (B, H, 1, D) in q.dtype.

    mode="reference" is the jnp einsum oracle (the pre-subsystem decode
    path, bitwise); the pallas modes run the split-KV kernel whose split
    size comes from the resolved ``attention_decode`` policy.
    """
    b, h, _, d = q.shape
    hkv, slots = k.shape[1], k.shape[2]
    group = h // hkv
    qg = q.reshape(b, hkv, group, d)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (b,))
    interpret = interpret_for(mode)
    if mode == "reference":
        out = decode_ref(qg, k, v, lengths, window=window,
                         logit_scale=logit_scale, softcap=softcap,
                         sinks=sinks)
    else:
        if policy is None:
            epilogue = AttnEpilogue(
                softcap=float(softcap) if softcap else 0.0,
                sink=sinks is not None)
            policy = resolve_decode_policy(b, hkv, group, slots, d, q.dtype,
                                           epilogue=epilogue)
        if obs.enabled():
            sig = autotune.OpSignature("attention_decode",
                                       (b, hkv, group, slots, d),
                                       str(q.dtype), epilogue=policy.epilogue)
            obs.launch("attention_decode",
                       grid=(b, hkv, max(1, slots // policy.block_kv)),
                       policy=policy,
                       dma_bytes=autotune.score_policy(sig, policy).dma_bytes,
                       flops=4 * b * h * slots * d)
        out = flash_decode(qg, k, v, lengths, policy=policy, window=window,
                           logit_scale=logit_scale,
                           softcap=float(softcap) if softcap else 0.0,
                           sinks=sinks, interpret=interpret)
    return out.reshape(b, h, 1, d)


def attention_decode_paged(q, k_pages, v_pages, page_table, lengths, *,
                           window: int | None = None,
                           policy: KernelPolicy | None = None,
                           logit_scale: float | None = None,
                           softcap: float | None = None, sinks=None,
                           mode: str = "pallas_interpret"):
    """Decode attention (1 or T query tokens) over a paged KV pool.

    q: (B, H, T, D) — T == 1 is plain decode; T > 1 is the speculative
    verify step, where token t of sequence b sits at absolute position
    ``lengths[b] - T + t`` (i.e. ``lengths`` counts the KV *including* the
    T verify tokens already appended). k_pages/v_pages:
    (P, Hkv, page_size, D); page_table: (B, MP) physical page ids (0 =
    reserved null page); lengths: (B,). ``softcap``/``sinks`` follow
    :func:`attention`. Returns (B, H, T, D) in q.dtype. mode="reference"
    gathers the pages into a contiguous view and runs the einsum oracle.
    """
    b, h, t, d = q.shape
    hkv, page_size = k_pages.shape[1], k_pages.shape[2]
    mp = page_table.shape[1]
    group = h // hkv
    if t == 1:
        qg = q.reshape(b, hkv, group, d)
    else:
        # pack verify tokens group-major: row = g*T + t
        qg = q.reshape(b, hkv, group, t, d).reshape(b, hkv, group * t, d)
        if sinks is not None:
            sinks = jnp.repeat(jnp.asarray(sinks).reshape(hkv, group), t,
                               axis=1).reshape(-1)
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32).reshape(-1),
                               (b,))
    interpret = interpret_for(mode)
    if mode == "reference":
        # function-level import: serve sits above kernels in the layering
        from repro.serve.kv_cache import gather_pages

        out = decode_ref(qg, gather_pages(k_pages, page_table),
                         gather_pages(v_pages, page_table), lengths,
                         window=window, logit_scale=logit_scale,
                         softcap=softcap, sinks=sinks, q_tokens=t)
    else:
        if policy is None:
            epilogue = AttnEpilogue(
                softcap=float(softcap) if softcap else 0.0,
                sink=sinks is not None)
            policy = resolve_decode_policy(b, hkv, group, mp * page_size, d,
                                           q.dtype, page_size=page_size,
                                           epilogue=epilogue, q_tokens=t)
        if obs.enabled():
            sig = autotune.OpSignature("attention_decode",
                                       (b, hkv, group * t, mp * page_size, d),
                                       str(q.dtype), epilogue=policy.epilogue)
            ppb = policy.block_kv // page_size
            obs.launch("attention_decode", variant="paged",
                       grid=(b, hkv // paged_heads_per_step(hkv, t),
                             -(-mp // ppb)), policy=policy,
                       dma_bytes=autotune.score_policy(sig, policy).dma_bytes,
                       flops=4 * b * h * t * mp * page_size * d)
        out = flash_decode_paged(qg, k_pages, v_pages, page_table, lengths,
                                 policy=policy, window=window,
                                 logit_scale=logit_scale,
                                 softcap=float(softcap) if softcap else 0.0,
                                 sinks=sinks,
                                 interpret=interpret,
                                 q_tokens=t)
    return out.reshape(b, h, t, d)
