"""Parameter declaration machinery + shared numerics.

A model is described by a flat dict ``{path: ParamDef}`` — one source of
truth for (a) initialization, (b) logical sharding axes, (c) the dry-run's
ShapeDtypeStructs. The nested param pytree is derived from the flat paths.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Optional

import jax
import jax.numpy as jnp

from repro import obs


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]   # logical axis names, len == len(shape)
    init: str = "normal"              # 'normal' | 'zeros' | 'ones' | 'lru_a'
    scale: float = 1.0                # stddev multiplier (normal init)
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def nest(flat: Mapping[str, object]) -> dict:
    """{'a/b/c': v} -> {'a': {'b': {'c': v}}}"""
    tree: dict = {}
    for path, value in flat.items():
        parts = path.split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return tree


def init_params(defs: Mapping[str, ParamDef], rng: jax.Array) -> dict:
    keys = jax.random.split(rng, max(1, len(defs)))
    flat = {}
    for key, (path, d) in zip(keys, sorted(defs.items())):
        dtype = jnp.dtype(d.dtype)
        if d.init == "zeros":
            flat[path] = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            flat[path] = jnp.ones(d.shape, dtype)
        elif d.init == "lru_a":
            # RG-LRU Λ init: a = sigmoid(Λ) uniform in [0.9, 0.999] (Griffin)
            u = jax.random.uniform(key, d.shape, jnp.float32, 0.9, 0.999)
            flat[path] = jnp.log(u / (1 - u)).astype(dtype)
        else:
            # the input dim of a matrix, also under a leading layer or
            # expert axis: (L, d_in, d_out) has fan-in d_in, not L
            fan_in = d.shape[-2] if len(d.shape) > 1 else d.shape[-1]
            std = d.scale / math.sqrt(max(1, fan_in))
            # drawn in the param dtype, so a bf16 stack never holds an f32
            # copy of itself
            flat[path] = (jax.random.normal(key, d.shape, dtype) * std
                          ).astype(dtype)
    return nest(flat)


def abstract_params(defs: Mapping[str, ParamDef]) -> dict:
    return nest({p: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype))
                 for p, d in defs.items()})


def logical_axes(defs: Mapping[str, ParamDef]) -> dict:
    return nest({p: d.axes for p, d in defs.items()})


def param_bytes(defs: Mapping[str, ParamDef]) -> int:
    return sum(math.prod(d.shape) * jnp.dtype(d.dtype).itemsize
               for d in defs.values())


def cast_params(params, dtype):
    """Cast float params to the compute dtype (fp32 masters live in the
    train state; norms/softmax upcast internally regardless)."""
    dtype = jnp.dtype(dtype)
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)


# ---------------------------------------------------------------------------
# Shared numerics (always fp32 internally).
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    c = xf - mean
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    out = c * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def apply_norm(cfg, x, p, prefix: str):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p[f"{prefix}_scale"])
    return layernorm(x, p[f"{prefix}_scale"], p.get(f"{prefix}_bias"))


def norm_params(p, prefix: str) -> tuple:
    """The (scale, bias) pair of a norm's params, for the ``prenorm``
    argument of mlp_forward / attention_layer (DESIGN.md §10): blocks hand
    the *pre-norm* residual stream plus these params to the layer, and the
    fused paths fold the norm into the first GEMM's A-tile prologue."""
    return (p[f"{prefix}_scale"], p.get(f"{prefix}_bias"))


def apply_prenorm(cfg, x, prenorm: tuple):
    """Standalone fallback for a ``prenorm`` pair — identical math to
    apply_norm (the prologue's oracle)."""
    # eager jnp, invisible to the kernel-launch journal — the counter is how
    # "no standalone norm ran" is asserted through obs.capture()
    obs.incr("model.standalone_norm")
    scale, bias = prenorm
    if getattr(cfg, "norm", "rmsnorm") == "rmsnorm":
        return rmsnorm(x, scale)
    return layernorm(x, scale, bias)


def resolve_norm_prologue(cfg, prenorm, *, kind, plan_shape, gemm_shape,
                          dtype, epilogue, residual=True):
    """The shared first rung of the prenorm fusion ladder (DESIGN.md §10),
    used by both the fused MLP and the fused QKV paths: fold the block's
    pre-norm into the first GEMM's A-tile prologue iff (a) the chain model
    picks the norm-fused plan from modeled dma_bytes and (b) a VMEM-legal
    prologue-carrying policy exists for that GEMM (the recompute path's
    full-K tile can be illegal for huge feature dims — the memoized
    select_policy probe discovers that).

    Returns (prologue, operand kwargs for gemm_fused, policy), or None —
    the caller then applies the standalone norm and scores the plain
    (norm-free) plan instead.
    """
    if prenorm is None:
        return None
    from repro.core import autotune
    from repro.kernels.gemm import norm_prologue

    norm_kind = getattr(cfg, "norm", "rmsnorm")
    plan = autotune.select_fusion(kind, plan_shape, dtype, residual=residual,
                                  prenorm=norm_kind)
    if plan["plan"] != "fused":
        return None
    scale, bias = prenorm
    pro = norm_prologue(norm_kind, beta=bias is not None)
    try:
        policy = autotune.select_policy("gemm", gemm_shape, dtype,
                                        epilogue=epilogue, prologue=pro)
    except ValueError:
        obs.incr(f"fallback.prologue_illegal.{kind}")
        return None
    kw = {"gamma": scale}
    if bias is not None:
        kw["beta"] = bias
    return pro, kw, policy


def act_fn(name: str):
    if name == "swiglu" or name == "silu":
        return jax.nn.silu
    if name == "geglu" or name == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(name)


# Config activation name -> epilogue activation name. Exhaustive on purpose:
# an activation act_fn doesn't know must not silently fuse as something else.
_EPILOGUE_ACT = {"swiglu": "silu", "silu": "silu",
                 "geglu": "gelu", "gelu": "gelu"}


def _act_name(mlp_act: str) -> str:
    if mlp_act not in _EPILOGUE_ACT:
        raise ValueError(mlp_act)
    return _EPILOGUE_ACT[mlp_act]


def _mlp_fused(cfg, p, x, *, residual, residual_scale, mode, gated,
               prenorm=None):
    """The fused-megakernel MLP (DESIGN.md §9-§10): the two gated
    up-projections run as ONE dual-output GEMM whose store applies
    act(x@w_gate)·(x@w_in), and the down-projection GEMM's store applies
    the scaled residual add — the (T, F) intermediate and the (T, D)
    output never round-trip HBM between ops. With ``prenorm`` (the block's
    (scale, bias) norm params) the pre-norm additionally folds into the up
    GEMM's A-tile prologue when the chain model picks that plan and the
    full-K tile is VMEM-legal; otherwise the standalone norm runs here and
    the rest of the chain still fuses. Returns None when no part of the
    chain fuses (stacked weights, or the chain model picks the eager plan)
    — the caller then owns the norm and the unfused chain.
    """
    from repro.core import autotune
    from repro.kernels.gemm import Epilogue, gemm_fused

    w_in = p["w_in"]
    if w_in.ndim != 2:
        return None  # stacked (scan-layout) weights: per-layer slices only
    *lead, d = x.shape
    f = w_in.shape[-1]
    tokens = math.prod(lead) if lead else 1
    has_res = residual is not None
    act = _act_name(cfg.mlp_act)
    up_ep = (Epilogue(activation=act, gate=True) if gated
             else Epilogue(activation=act))

    resolved = resolve_norm_prologue(
        cfg, prenorm, kind="mlp", plan_shape=(tokens, d, f, gated),
        gemm_shape=(tokens, f, d), dtype=str(x.dtype), epilogue=up_ep,
        residual=has_res)
    if resolved is None:
        plan = autotune.select_fusion("mlp", (tokens, d, f, gated),
                                      str(x.dtype), residual=has_res)
        if plan["plan"] != "fused":
            obs.incr("fallback.eager.mlp")
            return None
        if prenorm is not None:
            x = apply_prenorm(cfg, x, prenorm)  # standalone-norm fallback
        kw = {}
    else:
        prologue, pro_kw, up_policy = resolved
        kw = dict(prologue=prologue, policy=up_policy, **pro_kw)

    x2 = x.reshape(tokens, d)
    if gated:
        h = gemm_fused(x2, p["w_gate"], b2=w_in, epilogue=up_ep,
                       out_dtype=x.dtype, mode=mode, **kw)
    else:
        h = gemm_fused(x2, w_in, epilogue=up_ep,
                       out_dtype=x.dtype, mode=mode, **kw)
    if residual is None:
        y = gemm_fused(h, p["w_out"], epilogue=Epilogue(),
                       out_dtype=x.dtype, mode=mode)
    else:
        y = gemm_fused(h, p["w_out"],
                       epilogue=Epilogue(residual=True, scale=True),
                       residual=residual.reshape(tokens, d),
                       scale=residual_scale, out_dtype=x.dtype, mode=mode)
    return y.reshape(x.shape)


def mlp_forward(cfg, p, x, *, mode: str = "reference", residual=None,
                residual_scale: float = 1.0, prenorm=None):
    """Gated (swiglu/geglu) or plain MLP. p: params subtree with
    w_in/w_gate/w_out.

    With ``residual`` the returned value is ``residual + residual_scale *
    mlp(x)`` — callers pass their residual stream in so the pallas modes can
    fuse the add into the down-projection's store. With ``prenorm`` (the
    enclosing block's (scale, bias) norm params, see ``norm_params``) ``x``
    is the *pre-norm* residual stream and the returned value is
    ``residual + residual_scale * mlp(norm(x))`` — the pallas modes fold
    the norm into the up-projection GEMM's A-tile prologue (DESIGN.md §10)
    whenever the chain model picks that plan from modeled dma_bytes.
    'reference' keeps the original unfused jnp chain (the parity oracle).
    """
    gated = cfg.mlp_act in ("swiglu", "geglu")
    if mode != "reference":
        out = _mlp_fused(cfg, p, x, residual=residual,
                         residual_scale=residual_scale, mode=mode,
                         gated=gated, prenorm=prenorm)
        if out is not None:
            return out
    if prenorm is not None:
        x = apply_prenorm(cfg, x, prenorm)
    act = act_fn(cfg.mlp_act)
    if gated:
        h = act(x @ p["w_gate"]) * (x @ p["w_in"])
    else:
        h = act(x @ p["w_in"])
    m = h @ p["w_out"]
    if residual is None:
        return m
    return residual + residual_scale * m


def mlp_defs(cfg, prefix: str, *, stack: int | None = None,
             d_in: int | None = None, d_ff: int | None = None) -> dict:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    lead = (stack,) if stack else ()
    lax_ = ("layers",) if stack else ()
    dt = cfg.param_dtype
    defs = {f"{prefix}/w_in": ParamDef(lead + (d, f), lax_ + ("embed", "ffn"), dtype=dt),
            f"{prefix}/w_out": ParamDef(lead + (f, d), lax_ + ("ffn", "embed"), dtype=dt)}
    if cfg.mlp_act in ("swiglu", "geglu"):
        defs[f"{prefix}/w_gate"] = ParamDef(lead + (d, f), lax_ + ("embed", "ffn"), dtype=dt)
    return defs


def norm_defs(cfg, prefix: str, *, stack: int | None = None,
              width: int | None = None) -> dict:
    d = width or cfg.d_model
    lead = (stack,) if stack else ()
    lax_ = ("layers",) if stack else ()
    dt = cfg.param_dtype
    defs = {f"{prefix}_scale": ParamDef(lead + (d,), lax_ + (None,), init="ones", dtype=dt)}
    if cfg.norm == "layernorm":
        defs[f"{prefix}_bias"] = ParamDef(lead + (d,), lax_ + (None,), init="zeros", dtype=dt)
    return defs


def cross_entropy_loss(logits, labels, mask=None):
    """Mean CE over valid positions. logits (..., V) fp32-cast internally."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
