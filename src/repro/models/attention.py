"""GQA/MHA attention layer: projections + RoPE + flash kernel + KV cache.

Train/prefill route through the Pallas flash kernel (or its jnp oracle in
'reference' mode — the dry-run path). Single-token decode routes through
``attention_decode`` — the split-KV flash-decode kernel in the pallas
modes, its jnp einsum oracle in 'reference' mode (DESIGN.md §8).
Sliding-window archs (Mixtral SWA, RecurrentGemma local attention) keep a
ring-buffer cache of ``window`` slots so the 500k-decode cell stays O(window).

Two decode cache layouts coexist: the dense per-bucket (B, Hkv, S, D) cache
below, and the paged layout (``paged_*`` functions) whose physical pages
live in a shared pool managed by ``repro.serve.kv_cache`` — that one lets
sequences of different lengths share one compiled decode step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels.attention import (attention as attention_op,
                                     attention_decode,
                                     attention_decode_paged)
from repro.kernels.rope import rope as rope_op, rope_ref, rope_tables
from .common import ParamDef


def attn_defs(cfg, prefix: str, *, stack: int | None = None,
              cross: bool = False) -> dict:
    """q and k projections are stored PRE-PACKED as one ``wqk`` weight
    (d, (H+Hkv)·hd) — the fused QKV→RoPE megakernel projects q|k through
    one wide GEMM (DESIGN.md §9), and packing at param-build time removes
    the in-graph concat that used to be charged to the fused plan (a
    token-independent cost that made it lose at small token counts). The
    unfused paths slice the q/k halves back out (column slices of a GEMM
    are independent, so the math is unchanged). Same for ``bqk``."""
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    lead = (stack,) if stack else ()
    lx = ("layers",) if stack else ()
    dt = cfg.param_dtype
    kv_ax = "kv_heads" if getattr(cfg, "kv_shard", True) else None
    defs = {
        f"{prefix}/wqk": ParamDef(lead + (d, (h + hkv) * hd),
                                  lx + ("embed", "heads"), dtype=dt),
        f"{prefix}/wv": ParamDef(lead + (d, hkv * hd), lx + ("embed", kv_ax), dtype=dt),
        f"{prefix}/wo": ParamDef(lead + (h * hd, d), lx + ("heads", "embed"), dtype=dt),
    }
    if cfg.qkv_bias and not cross:
        defs[f"{prefix}/bqk"] = ParamDef(lead + ((h + hkv) * hd,),
                                         lx + ("heads",), init="zeros", dtype=dt)
        defs[f"{prefix}/bv"] = ParamDef(lead + (hkv * hd,), lx + (kv_ax,), init="zeros", dtype=dt)
    return defs


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * hd)


def _apply_rope(cfg, q, k, positions, mode: str):
    """q/k: (B, H, S, hd). positions: (S,) absolute positions."""
    if cfg.rope_style == "none":
        return q, k
    # any standalone (non-store-fused) rotation counts here, kernel or jnp
    obs.incr("model.standalone_rope")
    hd = q.shape[-1]
    rot = hd // 2 if cfg.rope_style == "partial" else hd
    sin, cos = rope_tables(positions, rot, cfg.rope_theta)

    def rot_fn(x):
        xr = x[..., :rot]
        # the Pallas rope kernel wants contiguous full-seq blocks; decode and
        # partial-dim cases use the (identical) jnp reference.
        if mode != "reference" and cfg.rope_style == "half" and xr.shape[2] >= 128:
            out = rope_op(xr, sin, cos, mode=mode)
        else:
            out = rope_ref(xr, sin, cos)
        if rot == hd:
            return out
        return jnp.concatenate([out, x[..., rot:]], axis=-1)

    return rot_fn(q), rot_fn(k)


def project_qkv(cfg, p, x, kv_input=None):
    """Unfused projections over the packed ``wqk`` weight: the q/k halves
    are column slices (independent GEMM columns — same math as separate
    wq/wk weights)."""
    nq = cfg.num_heads * cfg.head_dim
    kv_src = x if kv_input is None else kv_input
    if kv_input is None:
        qk = x @ p["wqk"]
        q, k = qk[..., :nq], qk[..., nq:]
    else:  # cross-attention: q and k project different streams
        q = x @ p["wqk"][..., :nq]
        k = kv_src @ p["wqk"][..., nq:]
    v = kv_src @ p["wv"]
    if "bqk" in p:
        q = q + p["bqk"][..., :nq]
        k = k + p["bqk"][..., nq:]
        v = v + p["bv"]
    q = _split_heads(q, cfg.num_heads, cfg.head_dim)
    k = _split_heads(k, cfg.num_kv_heads, cfg.head_dim)
    v = _split_heads(v, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def fused_project_qkv_rope(cfg, p, x, positions, mode, prenorm=None):
    """QKV projection with the RoPE rotation fused into the GEMM store
    (DESIGN.md §9) and, with ``prenorm``, the block's pre-norm fused into
    the GEMM's A-tile prologue (DESIGN.md §10): q and k project through ONE
    wide GEMM over the pre-packed ``wqk`` whose A tiles are normalized as
    they stream in and whose output tiles are rotated while still
    VMEM-resident — the normed activation and the rotated q/k never
    round-trip HBM. v projects through a (bias-only) fused GEMM with the
    same prologue.

    Applies only to full-rotation RoPE ('half' style) on per-layer (2-D)
    weights, and only when the autotuner's chain model picks the fused plan
    from modeled dma_bytes; returns None otherwise so callers fall back to
    the unfused oracle path (norm + project_qkv + _apply_rope). When the
    norm-prologue plan loses (or its full-K tile is VMEM-illegal) but the
    plain fused plan wins, the standalone norm runs here and the rest still
    fuses — a non-None return always means ``prenorm`` was consumed.
    """
    from repro.core import autotune
    from repro.kernels.gemm import Epilogue, gemm_fused
    from .common import apply_prenorm, resolve_norm_prologue

    if cfg.rope_style != "half" or p["wqk"].ndim != 2:
        return None
    b, s, d = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if positions.shape[0] != s:
        return None
    shape = (b * s, d, h, hkv, hd)
    has_bias = "bqk" in p
    qk_ep = Epilogue(bias=has_bias, rope=True, head_dim=hd)

    resolved = resolve_norm_prologue(
        cfg, prenorm, kind="qkv_rope", plan_shape=shape,
        gemm_shape=(b * s, (h + hkv) * hd, d), dtype=str(x.dtype),
        epilogue=qk_ep)
    if resolved is None:
        plan = autotune.select_fusion("qkv_rope", shape, str(x.dtype))
        if plan["plan"] != "fused":
            obs.incr("fallback.eager.qkv_rope")
            return None
        if prenorm is not None:
            x = apply_prenorm(cfg, x, prenorm)  # standalone-norm fallback
        qk_policy, kw = None, {}
    else:
        prologue, pro_kw, qk_policy = resolved
        kw = dict(prologue=prologue, **pro_kw)

    x2 = x.reshape(b * s, d)
    sin, cos = rope_tables(positions, hd, cfg.rope_theta)
    # one table row per flattened (batch, seq) token row of the GEMM
    sin_m = jnp.tile(sin, (b, 1))
    cos_m = jnp.tile(cos, (b, 1))
    qk = gemm_fused(x2, p["wqk"], epilogue=qk_ep, bias=p.get("bqk"),
                    sin=sin_m, cos=cos_m, policy=qk_policy,
                    out_dtype=x.dtype, mode=mode, **kw)
    v = gemm_fused(x2, p["wv"], epilogue=Epilogue(bias=has_bias),
                   bias=p.get("bv"), out_dtype=x.dtype, mode=mode, **kw)
    q = qk[:, : h * hd].reshape(b, s, h * hd)
    k = qk[:, h * hd:].reshape(b, s, hkv * hd)
    return (_split_heads(q, h, hd), _split_heads(k, hkv, hd),
            _split_heads(v.reshape(b, s, hkv * hd), hkv, hd))


def fused_project_qkv(cfg, p, x, mode, prenorm=None):
    """Rope-free fused QKV projection (DESIGN.md §10, §12): the packed q|k
    GEMM and the v GEMM each fold the block's pre-norm into their A-tile
    prologue, so BERT/Whisper/enc-dec self-attention blocks — whose
    ``rope_style`` disqualifies the rope-store fusion — stop paying the
    standalone-norm round trip.

    The rope-free fusion only *wins* through the folded norm (without a
    prenorm the fused and eager plans stream identical bytes), so this
    returns None unless ``prenorm`` is given AND the chain model picks the
    norm-fused 'qkv' plan AND a VMEM-legal prologue policy exists; callers
    then fall back to the standalone norm + ``project_qkv``. A non-None
    return always means ``prenorm`` was consumed.
    """
    from repro.kernels.gemm import Epilogue, gemm_fused
    from .common import resolve_norm_prologue

    if p["wqk"].ndim != 2:
        return None
    b, s, d = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    has_bias = "bqk" in p
    qk_ep = Epilogue(bias=has_bias)
    resolved = resolve_norm_prologue(
        cfg, prenorm, kind="qkv", plan_shape=(b * s, d, h, hkv, hd),
        gemm_shape=(b * s, (h + hkv) * hd, d), dtype=str(x.dtype),
        epilogue=qk_ep)
    if resolved is None:
        return None
    prologue, pro_kw, qk_policy = resolved
    kw = dict(prologue=prologue, **pro_kw)

    x2 = x.reshape(b * s, d)
    qk = gemm_fused(x2, p["wqk"], epilogue=qk_ep, bias=p.get("bqk"),
                    policy=qk_policy, out_dtype=x.dtype, mode=mode, **kw)
    v = gemm_fused(x2, p["wv"], epilogue=Epilogue(bias=has_bias),
                   bias=p.get("bv"), out_dtype=x.dtype, mode=mode, **kw)
    q = qk[:, : h * hd].reshape(b, s, h * hd)
    k = qk[:, h * hd:].reshape(b, s, hkv * hd)
    return (_split_heads(q, h, hd), _split_heads(k, hkv, hd),
            _split_heads(v.reshape(b, s, hkv * hd), hkv, hd))


def project_qkv_heads(cfg, p, x, positions=None, *, mode: str,
                      prenorm=None, use_rope: bool = True):
    """The self-attention QKV plan ladder (DESIGN.md §12), shared by
    ``attention_layer`` and the block-level prefill paths (lm/encdec):
    always returns rotated (q, k, v) heads and always consumes ``prenorm``.

    Rungs, each guarded by the chain model's modeled dma_bytes:
      1. ``fused_project_qkv_rope`` — norm + packed q|k GEMM + rope store,
         'half'-style rope only;
      2. ``fused_project_qkv`` + ``_apply_rope`` — the norm still folds
         into the packed GEMM when rope can't ride the store ('partial' /
         'none' styles, or the rope plan lost);
      3. standalone ``apply_prenorm`` + ``project_qkv`` + ``_apply_rope``
         (the reference path, and the pallas fallback).
    """
    from .common import apply_prenorm

    if use_rope and positions is None:
        positions = jnp.arange(x.shape[1])
    if mode != "reference":
        if use_rope and cfg.rope_style == "half":
            qkv = fused_project_qkv_rope(cfg, p, x, positions, mode,
                                         prenorm=prenorm)
            if qkv is not None:
                return qkv
        qkv = fused_project_qkv(cfg, p, x, mode, prenorm=prenorm)
        if qkv is not None:
            q, k, v = qkv
            if use_rope:
                q, k = _apply_rope(cfg, q, k, positions, mode)
            return q, k, v
    if prenorm is not None:
        x = apply_prenorm(cfg, x, prenorm)
    q, k, v = project_qkv(cfg, p, x)
    if use_rope:
        q, k = _apply_rope(cfg, q, k, positions, mode)
    return q, k, v


def attention_layer(cfg, p, x, *, causal: bool = True,
                    window: int | None = None, kv_input=None,
                    positions=None, mode: str = "reference",
                    use_rope: bool = True, policy=None, prenorm=None):
    """Full-sequence attention (train/prefill). x: (B, S, D).

    With ``prenorm`` (the enclosing block's (scale, bias) norm params, see
    ``common.norm_params``) ``x`` is the *pre-norm* residual stream: the
    pallas modes fold the norm into the fused QKV GEMM's A-tile prologue
    (DESIGN.md §10) when the chain model picks that plan; otherwise the
    standalone norm runs here before the projections. Self-attention
    resolves through the ``project_qkv_heads`` plan ladder (rope-fused,
    norm-fused rope-free, standalone); cross-attention (``kv_input``)
    keeps the standalone projections.

    ``cfg.attn_logit_softcap`` threads through to the attention op as its
    softcap epilogue stage (gemma2-style tanh cap, DESIGN.md §12).

    Block sizes are no longer hard-coded here: with ``policy=None`` the op
    resolves a KernelPolicy from the analytic autotuner per shape-bucket
    (memoized), so model-build-time resolution (models/api.py) and the
    trace-time call agree (DESIGN.md §5).
    """
    from .common import apply_prenorm

    if kv_input is None:
        q, k, v = project_qkv_heads(cfg, p, x, positions, mode=mode,
                                    prenorm=prenorm, use_rope=use_rope)
    else:
        if prenorm is not None:
            x = apply_prenorm(cfg, x, prenorm)
        q, k, v = project_qkv(cfg, p, x, kv_input)
    out = attention_op(q, k, v, causal=causal, window=window,
                       policy=policy, mode=mode,
                       softcap=getattr(cfg, "attn_logit_softcap", None))
    return _merge_heads(out) @ p["wo"]


# ---------------------------------------------------------------------------
# KV cache (decode path)
# ---------------------------------------------------------------------------

def cache_len(cfg, max_len: int, window: int | None) -> int:
    return min(max_len, window) if window else max_len


def init_attn_cache(cfg, batch: int, max_len: int, window: int | None,
                    dtype) -> dict:
    slots = cache_len(cfg, max_len, window)
    shape = (batch, cfg.num_kv_heads, slots, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill_attn_cache(cfg, cache: dict, k, v, seq_len: int,
                       window: int | None) -> dict:
    """Insert full-prefill k/v (B, Hkv, S, hd) into (possibly ring) cache."""
    slots = cache["k"].shape[2]
    if seq_len <= slots:
        k_c = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=2)
        v_c = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=2)
        return {"k": k_c, "v": v_c}
    # ring: keep the last ``slots`` positions at slot = pos % slots
    tail_k = k[:, :, -slots:]
    tail_v = v[:, :, -slots:]
    pos = jnp.arange(seq_len - slots, seq_len)
    idx = pos % slots
    k_c = cache["k"].at[:, :, idx].set(tail_k)
    v_c = cache["v"].at[:, :, idx].set(tail_v)
    return {"k": k_c, "v": v_c}


def decode_attention_layer(cfg, p, x, cache: dict, pos, *,
                           window: int | None = None, cross: bool = False,
                           update_cache: bool = True,
                           use_rope: bool = True, mode: str = "reference",
                           policy=None):
    """One-token decode. x: (B, 1, D); pos: scalar int32 (current position).

    ``cross=True``: q from x, k/v from the static (cross-attention) cache.
    ``mode`` selects the attention_decode implementation ('reference' is
    the einsum oracle; pallas modes run the split-KV flash-decode kernel).
    Returns (out (B,1,D), new_cache).
    """
    b = x.shape[0]
    if cross:
        nq = cfg.num_heads * cfg.head_dim
        q = x @ p["wqk"][..., :nq]
        if "bqk" in p:
            q = q + p["bqk"][..., :nq]
        q = _split_heads(q, cfg.num_heads, cfg.head_dim)
        k, v = cache["k"], cache["v"]  # static cross-attention cache
        lengths = jnp.full((b,), k.shape[2], jnp.int32)  # all slots valid
        window = None
    else:
        q, k_new, v_new = project_qkv(cfg, p, x)
        if use_rope:
            positions = jnp.asarray(pos).reshape(1)
            q, k_new = _apply_rope(cfg, q, k_new, positions, "reference")
        slots = cache["k"].shape[2]
        pos = jnp.asarray(pos, jnp.int32)
        slot = pos % slots
        if update_cache:
            k_c = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=2)
            v_c = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=2)
            cache = {"k": k_c, "v": v_c}
        k, v = cache["k"], cache["v"]
        lengths = jnp.broadcast_to(pos + 1, (b,))

    out = attention_decode(q, k, v, lengths, window=window, policy=policy,
                           softcap=getattr(cfg, "attn_logit_softcap", None),
                           mode=mode).astype(x.dtype)
    return _merge_heads(out) @ p["wo"], cache


# ---------------------------------------------------------------------------
# Paged KV cache (decode path over a shared page pool; DESIGN.md §8)
# ---------------------------------------------------------------------------

def init_paged_attn_cache(cfg, n_pages: int, page_size: int, dtype) -> dict:
    from repro.serve.kv_cache import init_page_pool
    return init_page_pool(n_pages, cfg.num_kv_heads, page_size,
                          cfg.head_dim, dtype)


def paged_prefill_attn_cache(cfg, cache: dict, k, v, page_rows,
                             start_page=0) -> dict:
    """Write one sequence's prefill k/v (1, Hkv, S, hd) into its pages.

    ``start_page`` (traced ok) offsets the write within the page-table row
    — chunk c of a chunked prefill passes its first page index."""
    from repro.serve.kv_cache import write_prefill_pages
    k_pages, v_pages = write_prefill_pages(cache["k_pages"], cache["v_pages"],
                                           k, v, page_rows,
                                           start_page=start_page)
    return {"k_pages": k_pages, "v_pages": v_pages}


def _apply_rope_positions(cfg, q, k, positions):
    """RoPE with per-batch-element positions (the paged decode step, where
    each sequence sits at its own length). q/k: (B, H, T, hd); positions:
    (B,) for T == 1, or (B, T) when each token carries its own position
    (chunked prefill / speculative verify). Matches ``_apply_rope``'s
    reference path exactly for uniform positions."""
    if cfg.rope_style == "none":
        return q, k
    hd = q.shape[-1]
    rot = hd // 2 if cfg.rope_style == "partial" else hd
    if positions.ndim == 1:
        sin, cos = rope_tables(positions, rot, cfg.rope_theta)
        sin, cos = sin[:, None, None, :], cos[:, None, None, :]
    else:
        b, t = positions.shape
        sin, cos = rope_tables(positions.reshape(-1), rot, cfg.rope_theta)
        sin, cos = (sin.reshape(b, 1, t, rot), cos.reshape(b, 1, t, rot))

    def rot_fn(x):
        out = rope_ref(x[..., :rot], sin, cos)
        if rot == hd:
            return out
        return jnp.concatenate([out, x[..., rot:]], axis=-1)

    return rot_fn(q), rot_fn(k)


def paged_decode_attention_layer(cfg, p, x, cache: dict, page_table, lengths,
                                 *, window: int | None = None,
                                 use_rope: bool = True,
                                 mode: str = "reference", policy=None):
    """Decode (1 or T tokens) over the paged cache. x: (B, T, D);
    ``lengths``: (B,) tokens written so far (token t lands at position
    lengths[b] + t; T > 1 is the speculative verify step). Inactive slots
    (empty page-table rows) write into the reserved null page and read back
    zeros. Returns (out (B,T,D), new_cache)."""
    from repro.serve.kv_cache import append_paged_kv
    t = x.shape[1]
    q, k_new, v_new = project_qkv(cfg, p, x)
    lengths = jnp.asarray(lengths, jnp.int32)
    if use_rope:
        positions = lengths if t == 1 else lengths[:, None] + jnp.arange(t)
        q, k_new = _apply_rope_positions(cfg, q, k_new, positions)
    k_pages, v_pages = append_paged_kv(cache["k_pages"], cache["v_pages"],
                                       k_new, v_new, page_table, lengths)
    cache = {"k_pages": k_pages, "v_pages": v_pages}
    out = attention_decode_paged(q, k_pages, v_pages, page_table, lengths + t,
                                 window=window, policy=policy,
                                 softcap=getattr(cfg, "attn_logit_softcap",
                                                 None),
                                 mode=mode).astype(x.dtype)
    return _merge_heads(out) @ p["wo"], cache
