"""Decoder-only LM assembly: dense / MoE / SSM / RG-LRU / local-attn blocks.

Uniform-pattern archs (llama-family, qwen2, mixtral, mamba2, ...) stack their
layer params with a leading 'layers' axis and run under one ``lax.scan`` so
the 80-layer qwen2-72b compiles to a small HLO. Hybrid archs
(recurrentgemma's 2:1 recurrent:attention pattern) unroll a python loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (ParamDef, apply_norm, cast_params, cross_entropy_loss,
                     init_params, mlp_defs, mlp_forward, norm_defs,
                     norm_params)
from .attention import (attn_defs, attention_layer, decode_attention_layer,
                        init_attn_cache, init_paged_attn_cache,
                        paged_decode_attention_layer, paged_prefill_attn_cache,
                        prefill_attn_cache, project_qkv_heads,
                        _merge_heads)
from repro.kernels.attention import attention as attention_op
from repro.kernels.attention import attention_decode_paged
from .moe import moe_defs, moe_forward
from .ssm import (ssm_defs, ssm_forward, ssm_prefill, ssm_decode_step,
                  init_ssm_cache)
from .rglru import (rglru_defs, rglru_forward, rglru_prefill,
                    rglru_decode_step, init_rglru_cache)


def _layout(cfg) -> tuple:
    """How layers are stacked for scan:
    ('scan', pattern, n_groups) — layers grouped by the block pattern and
    scanned (pattern length 1 = classic uniform stack); ('loop',) — unrolled
    python loop (pattern doesn't divide num_layers, e.g. recurrentgemma's
    26 = 8x3 + 2)."""
    kinds = [cfg.layer_kind(i) for i in range(cfg.num_layers)]
    if len(set(kinds)) == 1:
        return ("scan", (kinds[0],), cfg.num_layers)
    pat = tuple(cfg.block_pattern)
    if cfg.num_layers % len(pat) == 0:
        return ("scan", pat, cfg.num_layers // len(pat))
    return ("loop",)


def _is_uniform(cfg) -> bool:
    return _layout(cfg)[0] == "scan"


def _block_window(cfg, kind: str):
    if kind == "local":
        return (cfg.rglru.local_window if cfg.rglru is not None
                else cfg.attn_window)
    return cfg.attn_window


def block_defs(cfg, kind: str, prefix: str, *, stack=None) -> dict:
    defs = {}
    if kind in ("attn", "local", "moe"):
        defs.update(attn_defs(cfg, f"{prefix}/attn", stack=stack))
        defs.update(norm_defs(cfg, f"{prefix}/ln1", stack=stack))
        defs.update(norm_defs(cfg, f"{prefix}/ln2", stack=stack))
        if kind == "moe":
            defs.update(moe_defs(cfg, f"{prefix}/moe", stack=stack))
        else:
            defs.update(mlp_defs(cfg, f"{prefix}/mlp", stack=stack))
    elif kind == "ssm":
        defs.update(ssm_defs(cfg, f"{prefix}/ssm", stack=stack))
        defs.update(norm_defs(cfg, f"{prefix}/ln1", stack=stack))
    elif kind == "rg":
        defs.update(rglru_defs(cfg, f"{prefix}/rec", stack=stack))
        defs.update(mlp_defs(cfg, f"{prefix}/mlp", stack=stack))
        defs.update(norm_defs(cfg, f"{prefix}/ln1", stack=stack))
        defs.update(norm_defs(cfg, f"{prefix}/ln2", stack=stack))
    else:
        raise ValueError(f"unknown block kind {kind!r}")
    return defs


def lm_param_defs(cfg) -> dict:
    d, v = cfg.d_model, cfg.padded_vocab()
    dt = cfg.param_dtype
    emb_axes = (("vocab", "embed") if cfg.embed_shard == "vocab"
                else (None, "ffn"))  # 'ffn' -> model axis on the d dim
    if cfg.tie_embeddings and cfg.embed_shard != "vocab":
        raise ValueError("embed d-sharding requires an untied LM head "
                         "(tied logits would contract over a sharded dim)")
    defs = {"embed": ParamDef((v, d), emb_axes, dtype=dt)}
    layout = _layout(cfg)
    if layout[0] == "scan":
        _, pattern, n_groups = layout
        if len(pattern) == 1:
            defs.update(block_defs(cfg, pattern[0], "blocks", stack=n_groups))
        else:
            for i, kind in enumerate(pattern):
                defs.update(block_defs(cfg, kind, f"blocks_{i}",
                                       stack=n_groups))
    else:
        for i in range(cfg.num_layers):
            defs.update(block_defs(cfg, cfg.layer_kind(i), f"layer_{i:03d}"))
    defs.update(norm_defs(cfg, "final_norm"))
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, v), ("embed", "vocab"), dtype=dt)
    return defs


def _scan_params(cfg, params, layout):
    """xs pytree for lax.scan: tuple over pattern positions."""
    _, pattern, _ = layout
    if len(pattern) == 1:
        return (params["blocks"],)
    return tuple(params[f"blocks_{i}"] for i in range(len(pattern)))


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def block_forward(cfg, kind: str, p, x, *, positions=None,
                  mode: str = "reference", mesh=None, data_axes=("data",)):
    """Returns (x, aux_loss).

    The pre-norm residual stream routes *unnormed* into attention_layer /
    mlp_forward / moe_forward (``prenorm=`` carries the norm params): the
    pallas modes fold the ln1/ln2 norms into the QKV / MLP-up GEMM A-tile
    prologues (DESIGN.md §10), and the shard_map MoE paths norm the
    per-rank token slice inside the shard and run the fused expert FFN
    under collective tracing (DESIGN.md §16); reference mode applies the
    identical standalone norm inside the layer. Recurrent cores keep the
    standalone norm (non-GEMM chains, see ROADMAP deferred items).
    """
    aux = jnp.zeros((), jnp.float32)
    rs = cfg.residual_scale
    if kind in ("attn", "local", "moe"):
        a = attention_layer(cfg, p["attn"], x, causal=True,
                            window=_block_window(cfg, kind),
                            positions=positions, mode=mode,
                            prenorm=norm_params(p, "ln1"))
        x = x + rs * a
        if kind == "moe":
            m, aux = moe_forward(cfg, p["moe"], x, mesh=mesh,
                                 data_axes=data_axes, mode=mode,
                                 prenorm=norm_params(p, "ln2"))
            x = x + rs * m
        else:
            x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                            residual_scale=rs, prenorm=norm_params(p, "ln2"))
    elif kind == "ssm":
        h = apply_norm(cfg, x, p, "ln1")
        x = x + rs * ssm_forward(cfg, p["ssm"], h)
    elif kind == "rg":
        h = apply_norm(cfg, x, p, "ln1")
        x = x + rs * rglru_forward(cfg, p["rec"], h)
        x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                        residual_scale=rs, prenorm=norm_params(p, "ln2"))
    return x, aux


def _logits(cfg, params, x):
    x = apply_norm(cfg, x, params, "final_norm")
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
    if cfg.padded_vocab() != cfg.vocab_size:
        # mask the padding columns so they carry no probability mass
        pad_mask = jnp.arange(cfg.padded_vocab()) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits / cfg.logit_scale_div


def _remat(cfg, fn):
    if cfg.remat_policy == "none":
        return fn
    policy = None
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(fn, prevent_cse=False, policy=policy)


def lm_forward(cfg, params, tokens, *, mode: str = "reference", mesh=None,
               data_axes=("data",), remat: bool = False,
               return_hidden: bool = False):
    """tokens: (B, S) int32 -> logits (B, S, V) fp32 (or hidden states)."""
    layout = _layout(cfg)
    # a scanned layer stack is cast to the compute dtype one layer at a time
    # inside the scan body, so it is never held in both dtypes at once
    xs = _scan_params(cfg, params, layout) if layout[0] == "scan" else None
    params = cast_params({k: v for k, v in params.items()
                          if xs is None or not k.startswith("blocks")},
                         cfg.compute_dtype)
    x = params["embed"][tokens].astype(cfg.compute_dtype) * cfg.emb_scale
    positions = jnp.arange(tokens.shape[1])

    if layout[0] == "scan":
        _, pattern, _ = layout

        def body(carry, group_params):
            h, aux = carry
            group_params = cast_params(group_params, cfg.compute_dtype)
            for kind, layer_params in zip(pattern, group_params):
                h, aux_l = block_forward(cfg, kind, layer_params,
                                         h, positions=positions, mode=mode,
                                         mesh=mesh, data_axes=data_axes)
                aux = aux + aux_l
            return (h, aux), None

        if remat:
            body = _remat(cfg, body)
        from repro.util import scan_unroll
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   xs, unroll=scan_unroll())
    else:
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.num_layers):
            kind = cfg.layer_kind(i)
            fn = functools.partial(block_forward, cfg, kind,
                                   positions=positions, mode=mode, mesh=mesh,
                                   data_axes=data_axes)
            if remat:
                fn = _remat(cfg, fn)
            x, aux_l = fn(params[f"layer_{i:03d}"], x)
            aux = aux + aux_l
    if return_hidden:
        return x, aux
    return _logits(cfg, params, x), aux


def _chunked_ce(cfg, params, hidden, targets, mask, chunk: int):
    """CE over sequence chunks — the (B, S, V) logits are never materialized
    (per-chunk remat keeps the backward bounded too). §Perf lever."""
    from repro.util import scan_unroll
    b, s, d = hidden.shape
    while s % chunk:
        chunk //= 2
    nc = s // chunk
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)

    hs = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, inp):
        nll_sum, m_sum = carry
        h, t, m = inp
        logits = _logits(cfg, params, h)
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, t[..., None], axis=-1)[..., 0]
        mf = m.astype(jnp.float32)
        return (nll_sum + jnp.sum((lse - gold) * mf), m_sum + jnp.sum(mf)), None

    (nll, msum), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                  (hs, ts, ms), unroll=scan_unroll())
    return nll / jnp.maximum(msum, 1.0)


def lm_loss(cfg, params, batch, *, mode="reference", mesh=None,
            data_axes=("data",), remat: bool = True, aux_weight: float = 0.01):
    if cfg.ce_chunk:
        hidden, aux = lm_forward(cfg, params, batch["inputs"], mode=mode,
                                 mesh=mesh, data_axes=data_axes, remat=remat,
                                 return_hidden=True)
        ce = _chunked_ce(cfg, cast_params(params, cfg.compute_dtype), hidden,
                         batch["targets"], batch.get("loss_mask"),
                         cfg.ce_chunk)
    else:
        logits, aux = lm_forward(cfg, params, batch["inputs"], mode=mode,
                                 mesh=mesh, data_axes=data_axes, remat=remat)
        ce = cross_entropy_loss(logits, batch["targets"],
                                batch.get("loss_mask"))
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------

def _block_cache(cfg, kind, batch, max_len, dtype):
    if kind in ("attn", "local", "moe"):
        return init_attn_cache(cfg, batch, max_len, _block_window(cfg, kind),
                               dtype)
    if kind == "ssm":
        return init_ssm_cache(cfg, batch, dtype)
    if kind == "rg":
        return init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


def lm_init_cache(cfg, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.compute_dtype)
    layout = _layout(cfg)
    if layout[0] == "scan":
        _, pattern, n_groups = layout

        def stacked(kind):
            one = _block_cache(cfg, kind, batch, max_len, dtype)
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape),
                one)
        if len(pattern) == 1:
            return stacked(pattern[0])
        return {f"blocks_{i}": stacked(kind)
                for i, kind in enumerate(pattern)}
    return {f"layer_{i:03d}": _block_cache(cfg, cfg.layer_kind(i), batch,
                                           max_len, dtype)
            for i in range(cfg.num_layers)}


def _scan_cache(cfg, cache, layout):
    _, pattern, _ = layout
    if len(pattern) == 1:
        return (cache,)
    return tuple(cache[f"blocks_{i}"] for i in range(len(pattern)))


def _unscan_cache(cfg, cache_tuple, layout):
    _, pattern, _ = layout
    if len(pattern) == 1:
        return cache_tuple[0]
    return {f"blocks_{i}": c for i, c in enumerate(cache_tuple)}


def block_prefill(cfg, kind, p, x, cache, *, positions, mode="reference",
                  mesh=None, data_axes=("data",)):
    """Full-seq forward that also fills the decode cache. Returns (x, cache)."""
    s = x.shape[1]
    if kind in ("attn", "local", "moe"):
        window = _block_window(cfg, kind)
        # the same fused-QKV plan ladder as block_forward (DESIGN.md §12);
        # k comes back rotated, which is exactly the cache convention
        q, k, v = project_qkv_heads(cfg, p["attn"], x, positions, mode=mode,
                                    prenorm=norm_params(p, "ln1"))
        o = attention_op(q, k, v, causal=True, window=window, mode=mode,
                         softcap=getattr(cfg, "attn_logit_softcap", None))
        cache = prefill_attn_cache(cfg, cache, k, v, s, window)
        x = x + cfg.residual_scale * (_merge_heads(o) @ p["attn"]["wo"])
        if kind == "moe":
            m, _ = moe_forward(cfg, p["moe"], x, mesh=mesh,
                               data_axes=data_axes, mode=mode,
                               prenorm=norm_params(p, "ln2"))
            x = x + cfg.residual_scale * m
        else:
            x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                            residual_scale=cfg.residual_scale,
                            prenorm=norm_params(p, "ln2"))
    elif kind == "ssm":
        h = apply_norm(cfg, x, p, "ln1")
        o, cache = ssm_prefill(cfg, p["ssm"], h)
        x = x + cfg.residual_scale * o
    elif kind == "rg":
        h = apply_norm(cfg, x, p, "ln1")
        o, cache = rglru_prefill(cfg, p["rec"], h)
        x = x + cfg.residual_scale * o
        x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                        residual_scale=cfg.residual_scale,
                        prenorm=norm_params(p, "ln2"))
    return x, cache


def block_decode(cfg, kind, p, x, cache, pos, *, mode="reference", mesh=None,
                 data_axes=("data",)):
    rs = cfg.residual_scale
    if kind in ("attn", "local", "moe"):
        h = apply_norm(cfg, x, p, "ln1")
        a, cache = decode_attention_layer(cfg, p["attn"], h, cache, pos,
                                          window=_block_window(cfg, kind),
                                          mode=mode)
        x = x + rs * a
        if kind == "moe":
            m, _ = moe_forward(cfg, p["moe"], x, mesh=mesh,
                               data_axes=data_axes, mode=mode,
                               prenorm=norm_params(p, "ln2"))
            x = x + rs * m
        else:
            x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                            residual_scale=rs, prenorm=norm_params(p, "ln2"))
    elif kind == "ssm":
        h = apply_norm(cfg, x, p, "ln1")
        o, cache = ssm_decode_step(cfg, p["ssm"], h, cache)
        x = x + rs * o
    elif kind == "rg":
        h = apply_norm(cfg, x, p, "ln1")
        o, cache = rglru_decode_step(cfg, p["rec"], h, cache)
        x = x + rs * o
        x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                        residual_scale=rs, prenorm=norm_params(p, "ln2"))
    return x, cache


def lm_prefill(cfg, params, tokens, cache, *, mode="reference", mesh=None,
               data_axes=("data",)):
    """Returns (cache, last-position logits (B, V))."""
    params = cast_params(params, cfg.compute_dtype)
    x = params["embed"][tokens].astype(cfg.compute_dtype) * cfg.emb_scale
    positions = jnp.arange(tokens.shape[1])
    layout = _layout(cfg)
    if layout[0] == "scan":
        _, pattern, _ = layout

        def body(h, xs):
            group_params, group_cache = xs
            new = []
            for kind, layer_params, layer_cache in zip(pattern, group_params,
                                                       group_cache):
                h, nc = block_prefill(cfg, kind, layer_params, h,
                                      layer_cache, positions=positions,
                                      mode=mode, mesh=mesh,
                                      data_axes=data_axes)
                new.append(nc)
            return h, tuple(new)

        from repro.util import scan_unroll
        x, cache_t = jax.lax.scan(body, x, (_scan_params(cfg, params, layout),
                                            _scan_cache(cfg, cache, layout)),
                                  unroll=scan_unroll())
        cache = _unscan_cache(cfg, cache_t, layout)
    else:
        new = {}
        for i in range(cfg.num_layers):
            key = f"layer_{i:03d}"
            x, new[key] = block_prefill(cfg, cfg.layer_kind(i), params[key], x,
                                        cache[key], positions=positions,
                                        mode=mode, mesh=mesh,
                                        data_axes=data_axes)
        cache = new
    logits = _logits(cfg, params, x[:, -1:, :])
    return cache, logits[:, 0]


def lm_decode_step(cfg, params, token, cache, pos, *, mode="reference",
                   mesh=None, data_axes=("data",)):
    """token: (B, 1) int32; pos: scalar. Returns (cache, logits (B, V))."""
    params = cast_params(params, cfg.compute_dtype)
    x = params["embed"][token].astype(cfg.compute_dtype) * cfg.emb_scale
    layout = _layout(cfg)
    if layout[0] == "scan":
        _, pattern, _ = layout

        def body(h, xs):
            group_params, group_cache = xs
            new = []
            for kind, layer_params, layer_cache in zip(pattern, group_params,
                                                       group_cache):
                h, nc = block_decode(cfg, kind, layer_params, h,
                                     layer_cache, pos, mode=mode, mesh=mesh,
                                     data_axes=data_axes)
                new.append(nc)
            return h, tuple(new)

        from repro.util import scan_unroll
        x, cache_t = jax.lax.scan(body, x, (_scan_params(cfg, params, layout),
                                            _scan_cache(cfg, cache, layout)),
                                  unroll=scan_unroll())
        cache = _unscan_cache(cfg, cache_t, layout)
    else:
        new = {}
        for i in range(cfg.num_layers):
            key = f"layer_{i:03d}"
            x, new[key] = block_decode(cfg, cfg.layer_kind(i), params[key], x,
                                       cache[key], pos, mode=mode, mesh=mesh,
                                       data_axes=data_axes)
        cache = new
    logits = _logits(cfg, params, x)
    return cache, logits[:, 0]


# ---------------------------------------------------------------------------
# Paged decode path (shared page pool; DESIGN.md §8)
# ---------------------------------------------------------------------------

def _block_paged_cache(cfg, kind, batch_slots, n_pages, page_size, dtype):
    """Attention layers share a physical page pool; recurrent layers keep
    their constant-size per-slot state (continuous batching resets a slot's
    state at admission, so no paging is needed there)."""
    if kind in ("attn", "local", "moe"):
        return init_paged_attn_cache(cfg, n_pages, page_size, dtype)
    if kind == "ssm":
        return init_ssm_cache(cfg, batch_slots, dtype)
    if kind == "rg":
        return init_rglru_cache(cfg, batch_slots, dtype)
    raise ValueError(kind)


def lm_init_paged_cache(cfg, batch_slots: int, n_pages: int, page_size: int):
    """Paged analogue of :func:`lm_init_cache`: same pytree layout, but
    attention leaves are (n_pages, Hkv, page_size, hd) pools instead of
    (B, Hkv, max_len, hd) dense caches."""
    dtype = jnp.dtype(cfg.compute_dtype)
    layout = _layout(cfg)

    def one(kind):
        return _block_paged_cache(cfg, kind, batch_slots, n_pages,
                                  page_size, dtype)

    if layout[0] == "scan":
        _, pattern, n_groups = layout

        def stacked(kind):
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape),
                one(kind))
        if len(pattern) == 1:
            return stacked(pattern[0])
        return {f"blocks_{i}": stacked(kind)
                for i, kind in enumerate(pattern)}
    return {f"layer_{i:03d}": one(cfg.layer_kind(i))
            for i in range(cfg.num_layers)}


def block_prefill_paged(cfg, kind, p, x, cache, *, page_rows, slot,
                        positions, mode="reference", mesh=None,
                        data_axes=("data",)):
    """Single-sequence (B=1) prefill that fills the paged cache: attention
    k/v land in the sequence's pages; recurrent state lands in its batch
    slot. Returns (x, cache)."""
    if kind in ("attn", "local", "moe"):
        window = _block_window(cfg, kind)
        # same fused plan ladder as the dense block_prefill; rotated k
        # lands in the pages (the cache convention)
        q, k, v = project_qkv_heads(cfg, p["attn"], x, positions, mode=mode,
                                    prenorm=norm_params(p, "ln1"))
        o = attention_op(q, k, v, causal=True, window=window, mode=mode,
                         softcap=getattr(cfg, "attn_logit_softcap", None))
        cache = paged_prefill_attn_cache(cfg, cache, k, v, page_rows)
        x = x + cfg.residual_scale * (_merge_heads(o) @ p["attn"]["wo"])
        if kind == "moe":
            m, _ = moe_forward(cfg, p["moe"], x, mesh=mesh,
                               data_axes=data_axes, mode=mode,
                               prenorm=norm_params(p, "ln2"))
            x = x + cfg.residual_scale * m
        else:
            x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                            residual_scale=cfg.residual_scale,
                            prenorm=norm_params(p, "ln2"))
    elif kind == "ssm":
        h = apply_norm(cfg, x, p, "ln1")
        o, state = ssm_prefill(cfg, p["ssm"], h)
        cache = jax.tree.map(lambda c, s: c.at[slot].set(s[0]), cache, state)
        x = x + cfg.residual_scale * o
    elif kind == "rg":
        h = apply_norm(cfg, x, p, "ln1")
        o, state = rglru_prefill(cfg, p["rec"], h)
        cache = jax.tree.map(lambda c, s: c.at[slot].set(s[0]), cache, state)
        x = x + cfg.residual_scale * o
        x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                        residual_scale=cfg.residual_scale,
                        prenorm=norm_params(p, "ln2"))
    return x, cache


def lm_prefill_paged(cfg, params, tokens, cache, page_rows, slot, true_len,
                     *, mode="reference", mesh=None, data_axes=("data",)):
    """Prefill ONE sequence into the shared paged cache.

    tokens: (1, S); ``page_rows``: (max_pages,) page-table row; ``slot``:
    the sequence's batch slot (recurrent state lands there). Returns
    (cache, logits (1, V) at position ``true_len - 1``).

    S may exceed ``true_len`` (a padded bucket) ONLY for attention-only
    stacks: attention k/v past true_len stay masked by the length until
    overwritten, but ssm/rglru prefill state is the *final* scan state and
    would absorb the pad positions — callers serving recurrent/hybrid archs
    (PagedEngine does) must pass exact-length tokens (S == true_len).
    """
    params = cast_params(params, cfg.compute_dtype)
    x = params["embed"][tokens].astype(cfg.compute_dtype) * cfg.emb_scale
    positions = jnp.arange(tokens.shape[1])
    kw = dict(page_rows=page_rows, slot=slot, positions=positions, mode=mode,
              mesh=mesh, data_axes=data_axes)
    layout = _layout(cfg)
    if layout[0] == "scan":
        _, pattern, _ = layout

        def body(h, xs):
            group_params, group_cache = xs
            new = []
            for kind, layer_params, layer_cache in zip(pattern, group_params,
                                                       group_cache):
                h, nc = block_prefill_paged(cfg, kind, layer_params, h,
                                            layer_cache, **kw)
                new.append(nc)
            return h, tuple(new)

        from repro.util import scan_unroll
        x, cache_t = jax.lax.scan(body, x, (_scan_params(cfg, params, layout),
                                            _scan_cache(cfg, cache, layout)),
                                  unroll=scan_unroll())
        cache = _unscan_cache(cfg, cache_t, layout)
    else:
        new = {}
        for i in range(cfg.num_layers):
            key = f"layer_{i:03d}"
            x, new[key] = block_prefill_paged(cfg, cfg.layer_kind(i),
                                              params[key], x, cache[key],
                                              **kw)
        cache = new
    x_last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
    logits = _logits(cfg, params, x_last)
    return cache, logits[:, 0]


def _attention_only(cfg) -> bool:
    """True when every layer is attention-family (attn/local/moe blocks).

    The serving fast paths — chunked prefill, prefix reuse, multi-token
    verify — all rely on the KV cache being position-addressable pages.
    Recurrent state (ssm/rg) is a single constant-size scan state per slot:
    it cannot be re-entered mid-prompt, shared by prefix, or stepped T
    tokens at once, so those stacks keep the exact-length one-shot paths.
    """
    return all(cfg.layer_kind(i) in ("attn", "local", "moe")
               for i in range(cfg.num_layers))


def block_prefill_paged_chunk(cfg, kind, p, x, cache, *, page_rows, start,
                              positions, mode="reference", mesh=None,
                              data_axes=("data",)):
    """One layer of chunked prefill: the chunk's k/v land in the sequence's
    pages at page offset ``start // page_size`` and the chunk's queries
    attend to everything already in the pages (previous chunks + this one)
    through the multi-token paged-decode mask. Attention-family only."""
    window = _block_window(cfg, kind)
    c = x.shape[1]
    q, k, v = project_qkv_heads(cfg, p["attn"], x, positions, mode=mode,
                                prenorm=norm_params(p, "ln1"))
    page_size = cache["k_pages"].shape[2]
    cache = paged_prefill_attn_cache(cfg, cache, k, v, page_rows,
                                     start_page=start // page_size)
    o = attention_decode_paged(
        q, cache["k_pages"], cache["v_pages"],
        jnp.asarray(page_rows, jnp.int32)[None, :],
        jnp.asarray(start + c, jnp.int32).reshape(1),
        window=window, mode=mode,
        softcap=getattr(cfg, "attn_logit_softcap", None)).astype(x.dtype)
    x = x + cfg.residual_scale * (_merge_heads(o) @ p["attn"]["wo"])
    if kind == "moe":
        m, _ = moe_forward(cfg, p["moe"], x, mesh=mesh,
                           data_axes=data_axes, mode=mode,
                           prenorm=norm_params(p, "ln2"))
        x = x + cfg.residual_scale * m
    else:
        x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                        residual_scale=cfg.residual_scale,
                        prenorm=norm_params(p, "ln2"))
    return x, cache


def lm_prefill_paged_chunk(cfg, params, tokens, cache, page_rows, start,
                           last_index, *, mode="reference", mesh=None,
                           data_axes=("data",)):
    """Prefill ONE chunk of one sequence into the shared paged cache.

    tokens: (1, C) — chunk C must be a whole number of pages; ``start``
    (traced ok) is the chunk's first absolute position (a page multiple);
    ``last_index`` (traced ok) indexes the final true token within the
    chunk (its logits seed sampling — meaningful on the last chunk only).
    One compiled instance per chunk length C serves every chunk index and
    every suffix offset: prefix-cache admission reuses it with ``start`` =
    the matched prefix length. Returns (cache, logits (1, V)).

    Attention-family stacks only (see :func:`_attention_only`): recurrent
    state cannot be re-entered mid-prompt, so hybrid archs keep the
    exact-length :func:`lm_prefill_paged`.
    """
    if not _attention_only(cfg):
        raise ValueError(
            "chunked paged prefill requires an attention-only stack; "
            f"{cfg.name} has recurrent layers — use lm_prefill_paged")
    params = cast_params(params, cfg.compute_dtype)
    x = params["embed"][tokens].astype(cfg.compute_dtype) * cfg.emb_scale
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(tokens.shape[1])
    kw = dict(page_rows=page_rows, start=start, positions=positions,
              mode=mode, mesh=mesh, data_axes=data_axes)
    layout = _layout(cfg)
    if layout[0] == "scan":
        _, pattern, _ = layout

        def body(h, xs):
            group_params, group_cache = xs
            new = []
            for kind, layer_params, layer_cache in zip(pattern, group_params,
                                                       group_cache):
                h, nc = block_prefill_paged_chunk(cfg, kind, layer_params, h,
                                                  layer_cache, **kw)
                new.append(nc)
            return h, tuple(new)

        from repro.util import scan_unroll
        x, cache_t = jax.lax.scan(body, x, (_scan_params(cfg, params, layout),
                                            _scan_cache(cfg, cache, layout)),
                                  unroll=scan_unroll())
        cache = _unscan_cache(cfg, cache_t, layout)
    else:
        new = {}
        for i in range(cfg.num_layers):
            key = f"layer_{i:03d}"
            x, new[key] = block_prefill_paged_chunk(cfg, cfg.layer_kind(i),
                                                    params[key], x,
                                                    cache[key], **kw)
        cache = new
    x_last = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    logits = _logits(cfg, params, x_last)
    return cache, logits[:, 0]


def block_decode_paged(cfg, kind, p, x, cache, page_table, lengths, *,
                       mode="reference", mesh=None, data_axes=("data",)):
    rs = cfg.residual_scale
    if kind in ("attn", "local", "moe"):
        h = apply_norm(cfg, x, p, "ln1")
        a, cache = paged_decode_attention_layer(
            cfg, p["attn"], h, cache, page_table, lengths,
            window=_block_window(cfg, kind), mode=mode)
        x = x + rs * a
        if kind == "moe":
            m, _ = moe_forward(cfg, p["moe"], x, mesh=mesh,
                               data_axes=data_axes, mode=mode,
                               prenorm=norm_params(p, "ln2"))
            x = x + rs * m
        else:
            x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                            residual_scale=rs, prenorm=norm_params(p, "ln2"))
    elif kind == "ssm":
        h = apply_norm(cfg, x, p, "ln1")
        o, cache = ssm_decode_step(cfg, p["ssm"], h, cache)
        x = x + rs * o
    elif kind == "rg":
        h = apply_norm(cfg, x, p, "ln1")
        o, cache = rglru_decode_step(cfg, p["rec"], h, cache)
        x = x + rs * o
        x = mlp_forward(cfg, p["mlp"], x, mode=mode, residual=x,
                        residual_scale=rs, prenorm=norm_params(p, "ln2"))
    return x, cache


def lm_decode_step_paged(cfg, params, token, cache, page_table, lengths, *,
                         mode="reference", mesh=None, data_axes=("data",)):
    """One decode step for every batch slot over the paged cache.

    token: (B, T) int32 — T == 1 is plain decode (each slot's token lands
    at position lengths[b], logits return as (B, V)); T > 1 is the
    speculative verify step (token t lands at lengths[b] + t, logits
    return as (B, T, V); attention-only stacks). Inactive slots decode
    against the null page and produce ignorable logits.
    """
    if token.shape[1] > 1 and not _attention_only(cfg):
        raise ValueError(
            "multi-token paged decode (speculative verify) requires an "
            f"attention-only stack; {cfg.name} has recurrent layers")
    params = cast_params(params, cfg.compute_dtype)
    x = params["embed"][token].astype(cfg.compute_dtype) * cfg.emb_scale
    layout = _layout(cfg)
    if layout[0] == "scan":
        _, pattern, _ = layout

        def body(h, xs):
            group_params, group_cache = xs
            new = []
            for kind, layer_params, layer_cache in zip(pattern, group_params,
                                                       group_cache):
                h, nc = block_decode_paged(cfg, kind, layer_params, h,
                                           layer_cache, page_table, lengths,
                                           mode=mode, mesh=mesh,
                                           data_axes=data_axes)
                new.append(nc)
            return h, tuple(new)

        from repro.util import scan_unroll
        x, cache_t = jax.lax.scan(body, x, (_scan_params(cfg, params, layout),
                                            _scan_cache(cfg, cache, layout)),
                                  unroll=scan_unroll())
        cache = _unscan_cache(cfg, cache_t, layout)
    else:
        new = {}
        for i in range(cfg.num_layers):
            key = f"layer_{i:03d}"
            x, new[key] = block_decode_paged(cfg, cfg.layer_kind(i),
                                            params[key], x, cache[key],
                                            page_table, lengths, mode=mode,
                                            mesh=mesh, data_axes=data_axes)
        cache = new
    logits = _logits(cfg, params, x)
    if token.shape[1] > 1:
        return cache, logits          # (B, T, V) — speculative verify
    return cache, logits[:, 0]
