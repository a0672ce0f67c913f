"""Mixture-of-Experts FFN: top-k routing with two implementations.

* ``dense`` — every expert computed for every token (tiny smoke configs only).
* ``ep``    — production expert parallelism via shard_map: tokens are
  sequence-split across the 'model' axis, dispatched into capacity buckets,
  all_to_all'd to their expert's owner, FFN'd with the locally-resident
  expert weights, all_to_all'd back and combined. This is the standard
  MoE a2a pattern (Switch/COMET) mapped to jax.lax collectives per the
  hardware-adaptation rule in DESIGN.md.
"""
from __future__ import annotations

import functools
import math

import dataclasses
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro import obs
from .common import ParamDef, _act_name, act_fn, apply_prenorm

# Execution modes safe under collective tracing (shard_map). The interpret
# Pallas path traces fine inside shard_map on the forced-host-device harness;
# the real-TPU lowering has not been validated under collectives, so it
# observably falls back to the reference einsum (DESIGN.md §16).
_COLLECTIVE_SAFE_MODES = ("reference", "pallas_interpret")


def moe_defs(cfg, prefix: str, *, stack: int | None = None) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    lead = (stack,) if stack else ()
    lx = ("layers",) if stack else ()
    dt = cfg.param_dtype
    if cfg.moe.shard == "expert":          # EP: expert dim over the model axis
        in_axes = lx + ("expert", "embed", None)
        out_axes = lx + ("expert", None, "embed")
    else:                                   # TP: FFN hidden dim over model axis
        in_axes = lx + (None, "embed", "ffn")
        out_axes = lx + (None, "ffn", "embed")
    defs = {
        f"{prefix}/router": ParamDef(lead + (d, e), lx + ("embed", None), dtype=dt),
        f"{prefix}/w_in": ParamDef(lead + (e, d, f), in_axes, dtype=dt),
        f"{prefix}/w_out": ParamDef(lead + (e, f, d), out_axes, dtype=dt),
    }
    if cfg.mlp_act in ("swiglu", "geglu"):
        defs[f"{prefix}/w_gate"] = ParamDef(lead + (e, d, f), in_axes, dtype=dt)
    return defs


def _full_k_policy(shape, dtype, epilogue):
    """A gemm policy with block_k pinned to the full contraction dim, or
    None when no such VMEM-legal policy exists. K-tile accumulation order is
    the only fp difference between the fused kernel and jnp.dot, so a full-K
    policy makes the fused path *bitwise* equal to the reference einsum —
    the property the shard_map paths need so fused-vs-reference parity holds
    through collectives (DESIGN.md §16)."""
    from repro.core import autotune

    _, _, k = shape
    try:
        pol = autotune.select_policy("gemm", shape, dtype, epilogue=epilogue)
    except ValueError:
        return None
    if pol.block_k == k:
        return pol
    pinned = dataclasses.replace(
        pol, schedule=dataclasses.replace(pol.schedule, block_k=k))
    return pinned if pinned.is_legal() else None


def _expert_ffn_fused(cfg, p, x, mode, shard=None):
    """Per-expert fused megakernel FFN (DESIGN.md §9): each expert's two
    up-projections run as one dual-output GEMM (store applies the SwiGLU
    gating) followed by the down GEMM — the (T, F) expert intermediate
    never round-trips HBM. E is static, so the python loop unrolls into E
    independent kernel launches. Returns None when the autotuner's chain
    model picks the unfused plan. With ``shard`` (the enclosing shard_map's
    ShardSpec) the plan is scored with the collective chain term and both
    GEMMs run full-K policies so the fused path stays bitwise-equal to the
    reference oracle on every rank."""
    from repro.core import autotune
    from repro.kernels.gemm import Epilogue, gemm_fused

    e, t, d = x.shape
    f = p["w_in"].shape[-1]
    gated = cfg.mlp_act in ("swiglu", "geglu")
    # residual=False: the expert FFN chain has no residual add to eliminate
    plan = autotune.select_fusion("mlp", (t, d, f, gated), str(x.dtype),
                                  residual=False, shard=shard)
    if plan["plan"] != "fused":
        obs.incr("fallback.eager.moe")
        return None
    act = _act_name(cfg.mlp_act)
    up_ep = (Epilogue(activation=act, gate=True) if gated
             else Epilogue(activation=act))
    down_ep = Epilogue()
    up_pol = down_pol = None
    if shard is not None:
        up_pol = _full_k_policy((t, f, d), str(x.dtype), up_ep)
        down_pol = _full_k_policy((t, d, f), str(x.dtype), down_ep)
        if up_pol is None or down_pol is None:
            return None  # no bitwise-safe policy: reference path owns it
    outs = []
    for i in range(e):
        if gated:
            h = gemm_fused(x[i], p["w_gate"][i], b2=p["w_in"][i],
                           epilogue=up_ep, policy=up_pol,
                           out_dtype=x.dtype, mode=mode)
        else:
            h = gemm_fused(x[i], p["w_in"][i],
                           epilogue=up_ep, policy=up_pol,
                           out_dtype=x.dtype, mode=mode)
        outs.append(gemm_fused(h, p["w_out"][i], epilogue=down_ep,
                               policy=down_pol,
                               out_dtype=x.dtype, mode=mode))
    return jnp.stack(outs)


def _expert_ffn(cfg, p, x, mode: str = "reference", shard=None):
    """x: (E, T, D) grouped tokens; expert weights (E, D, F)/(E, F, D)."""
    if mode != "reference":
        out = _expert_ffn_fused(cfg, p, x, mode, shard=shard)
        if out is not None:
            return out
    act = act_fn(cfg.mlp_act)
    if cfg.mlp_act in ("swiglu", "geglu"):
        h = act(jnp.einsum("etd,edf->etf", x, p["w_gate"])) * \
            jnp.einsum("etd,edf->etf", x, p["w_in"])
    else:
        h = act(jnp.einsum("etd,edf->etf", x, p["w_in"]))
    return jnp.einsum("etf,efd->etd", h, p["w_out"])


def _route(cfg, x_flat, router_w):
    """x_flat: (T, D). Returns (weights (T,K), ids (T,K), aux_loss)."""
    logits = (x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    k = cfg.moe.top_k
    weights, ids = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing auxiliary loss
    e = cfg.moe.num_experts
    me = jnp.mean(jax.nn.one_hot(ids[:, 0], e), axis=0)
    ce = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(me * ce)
    return weights.astype(x_flat.dtype), ids, aux


def moe_dense(cfg, p, x, *, mode: str = "reference"):
    """All-experts einsum. x: (B, S, D). For reduced smoke configs."""
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    weights, ids, aux = _route(cfg, xf, p["router"])
    e = cfg.moe.num_experts
    outs = _expert_ffn(cfg, p, jnp.broadcast_to(xf, (e,) + xf.shape),
                       mode)  # (E,T,D)
    gate = jnp.zeros((xf.shape[0], e), x.dtype)
    gate = gate.at[jnp.arange(xf.shape[0])[:, None], ids].add(weights)
    out = jnp.einsum("te,etd->td", gate, outs)
    return out.reshape(b, s, d), aux


def _capacity(tokens_per_shard: int, cfg) -> int:
    c = math.ceil(tokens_per_shard * cfg.moe.top_k * cfg.moe.capacity_factor
                  / cfg.moe.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to sublane multiple


def _bspec(x, mesh, data_axes):
    """Batch-dim spec for shard_map: data axes when divisible, else None —
    the shared divisibility rule (distributed.sharding.divisible_axes)."""
    from repro.distributed.sharding import divisible_axes
    return divisible_axes(x.shape[0], mesh, data_axes or ())


def _gate_collective_mode(mode: str, impl: str, shard) -> str:
    """Capability gate for execution modes under shard_map. Unsafe modes
    fall back to the reference einsum *observably*: a counter plus a plan-
    audit event (§13), never a silent downgrade — the S2 fix for
    moe_ep/moe_tp historically dropping ``mode`` on the floor."""
    if mode in _COLLECTIVE_SAFE_MODES:
        return mode
    obs.incr("moe.collective_mode_fallback")
    obs.plan_decision(
        "collective_mode", f"moe_{impl}", (), "",
        {"mode": "reference", "requested": mode, "shard": shard.describe(),
         "reason": "mode not collective-safe"},
        [{"mode": m} for m in _COLLECTIVE_SAFE_MODES])
    return "reference"


def _prenorm_args(prenorm):
    """Flatten a (scale, bias-or-None) prenorm pair into explicit shard_map
    operands (closures over traced params are unsafe under shard_map) plus
    their replicated in_specs."""
    if prenorm is None:
        return (), ()
    scale, bias = prenorm
    args = (scale,) if bias is None else (scale, bias)
    return args, tuple(P(None) for _ in args)


def _apply_prenorm_args(cfg, t, norm):
    """Re-pair the flattened prenorm operands and apply to local tokens.
    The norm is rowwise, so norming the per-rank slice is bitwise-identical
    to slicing the normed full sequence — safe to push inside shard_map."""
    if not norm:
        return t
    pair = (norm[0], norm[1] if len(norm) > 1 else None)
    return apply_prenorm(cfg, t, pair)


def moe_ep(cfg, p, x, *, mesh, data_axes=("data",), model_axis="model",
           mode: str = "reference", prenorm=None):
    """Expert-parallel MoE. x: (B, S, D) sharded (data, None, None).

    Expert weights are sharded over ``model_axis`` (axis 0 = experts).
    Tokens are sequence-split across ``model_axis`` inside the shard, so each
    device routes S/ep_size of the sequence and the a2a volume per device is
    O(T/ep · D) — the COMET/Switch dispatch pattern.

    ``mode`` routes the per-rank expert FFN through the fused dual-GEMM
    megakernel (full-K policies — bitwise vs the reference einsum); unsafe
    modes fall back observably (``_gate_collective_mode``). ``prenorm`` is
    the block's (scale, bias) norm pair, applied to the per-rank token slice
    inside the shard (sequence-parallel norm: rowwise, so bitwise-identical
    to norm-then-slice).
    """
    from repro.distributed.sharding import ShardSpec

    e = cfg.moe.num_experts
    shard = ShardSpec.for_axis(mesh, model_axis, dim="expert",
                               collective="all_to_all")
    mode = _gate_collective_mode(mode, "ep", shard)
    bspec = _bspec(x, mesh, data_axes)
    norm_args, norm_specs = _prenorm_args(prenorm)
    in_specs = (P(bspec, None, None),                     # x
                P(None, None),                            # router (replicated)
                P(model_axis, None, None),                # w_in
                P(model_axis, None, None),                # w_out
                P(model_axis, None, None)) + norm_specs   # w_gate, norm
    out_specs = (P(bspec, None, None), P())

    has_gate = "w_gate" in p
    w_gate = p["w_gate"] if has_gate else p["w_in"]

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def inner(x, router, w_in, w_out, w_gate, *norm):
        ep = mesh.shape[model_axis]
        rank = jax.lax.axis_index(model_axis)
        bl, s, d = x.shape
        e_loc = e // ep
        seq_split = s % ep == 0 and s >= ep

        if seq_split:
            s_loc = s // ep
            xs = jax.lax.dynamic_slice_in_dim(x, rank * s_loc, s_loc, axis=1)
        else:
            xs = x  # tiny token counts (decode): route replicated
        t = xs.reshape(-1, d)                              # (T, D) local tokens
        t = _apply_prenorm_args(cfg, t, norm)
        weights, ids, aux = _route(cfg, t, router)
        cap = _capacity(t.shape[0], cfg)

        # slot assignment: token-major cumulative position per expert
        k = cfg.moe.top_k
        flat_ids = ids.reshape(-1)                         # (T*K,)
        onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot          # 1-based
        slot = jnp.sum(pos, axis=1) - 1                    # (T*K,)
        keep = (slot >= 0) & (slot < cap)

        buf = jnp.zeros((e, cap, d), x.dtype)
        tok_idx = jnp.repeat(jnp.arange(t.shape[0]), k)
        buf = buf.at[flat_ids, jnp.clip(slot, 0, cap - 1)].add(
            t[tok_idx] * keep[:, None].astype(x.dtype))

        ew = {"w_in": w_in, "w_out": w_out, "w_gate": w_gate}
        if seq_split:
            # dispatch: (E, C, D) -> (E_loc, ep*C, D) on the expert's owner
            recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                      concat_axis=1, tiled=True)
            out = _expert_ffn(cfg, ew, recv, mode, shard=shard)
            # return: (E_loc, ep*C, D) -> (E, C, D) back on the source rank
            back = jax.lax.all_to_all(out, model_axis, split_axis=1,
                                      concat_axis=0, tiled=True)
        else:
            # replicated dispatch: slice own experts, compute, all_gather
            mine = jax.lax.dynamic_slice_in_dim(buf, rank * e_loc, e_loc, axis=0)
            out = _expert_ffn(cfg, ew, mine, mode, shard=shard)
            back = jax.lax.all_gather(out, model_axis, axis=0, tiled=True)

        # combine: gather each token's k slots, weight, sum
        gathered = back.reshape(e * cap, d)[
            flat_ids * cap + jnp.clip(slot, 0, cap - 1)]
        gathered = gathered * (keep[:, None] * weights.reshape(-1)[:, None]
                               ).astype(x.dtype)
        y = jnp.sum(gathered.reshape(-1, k, d), axis=1)    # (T, D)
        if seq_split:
            ys = y.reshape(bl, s // ep, d)
            full = jax.lax.all_gather(ys, model_axis, axis=1, tiled=True)
        else:
            full = y.reshape(bl, s, d)
        aux = jax.lax.pmean(aux, model_axis)
        aux = jax.lax.pmean(aux, data_axes)
        return full, aux

    return inner(x, p["router"], p["w_in"], p["w_out"], w_gate, *norm_args)


def moe_tp(cfg, p, x, *, mesh, data_axes=("data",), model_axis="model",
           mode: str = "reference", prenorm=None):
    """Megatron-TP MoE: every expert's FFN hidden dim is sharded over the
    model axis; tokens are replicated across it. The block ends with one
    activation psum — the same wire cost as a dense Megatron MLP layer.
    Used when E < |model| (Mixtral's 8 experts on a 16-way axis).

    ``mode``/``prenorm`` as in :func:`moe_ep`: fused per-rank expert FFN
    (full-K, partial over the sharded F — identical psum operands to the
    reference path, so the collective preserves bitwise parity), norm
    applied to the replicated tokens inside the shard.
    """
    from repro.distributed.sharding import ShardSpec

    e = cfg.moe.num_experts
    shard = ShardSpec.for_axis(mesh, model_axis, dim="ffn",
                               collective="all_reduce")
    mode = _gate_collective_mode(mode, "tp", shard)
    bspec = _bspec(x, mesh, data_axes)
    norm_args, norm_specs = _prenorm_args(prenorm)
    in_specs = (P(bspec, None, None),
                P(None, None),
                P(None, None, model_axis),                # w_in: F sharded
                P(None, model_axis, None),                # w_out
                P(None, None, model_axis)) + norm_specs   # w_gate, norm
    out_specs = (P(bspec, None, None), P())
    has_gate = "w_gate" in p
    w_gate = p["w_gate"] if has_gate else p["w_in"]

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def inner(x, router, w_in, w_out, w_gate, *norm):
        bl, s, d = x.shape
        t = x.reshape(-1, d)
        t = _apply_prenorm_args(cfg, t, norm)
        weights, ids, aux = _route(cfg, t, router)
        cap = _capacity(t.shape[0], cfg)
        k = cfg.moe.top_k
        flat_ids = ids.reshape(-1)
        onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot
        slot = jnp.sum(pos, axis=1) - 1
        keep = (slot >= 0) & (slot < cap)
        buf = jnp.zeros((e, cap, d), x.dtype)
        tok_idx = jnp.repeat(jnp.arange(t.shape[0]), k)
        buf = buf.at[flat_ids, jnp.clip(slot, 0, cap - 1)].add(
            t[tok_idx] * keep[:, None].astype(x.dtype))

        out = _expert_ffn(cfg, {"w_in": w_in, "w_out": w_out,
                                "w_gate": w_gate}, buf, mode,
                          shard=shard)                    # partial over F
        gathered = out.reshape(e * cap, d)[
            flat_ids * cap + jnp.clip(slot, 0, cap - 1)]
        gathered = gathered * (keep[:, None] * weights.reshape(-1)[:, None]
                               ).astype(x.dtype)
        y = jnp.sum(gathered.reshape(-1, k, d), axis=1)
        y = jax.lax.psum(y, model_axis)                   # Megatron-style AR
        aux = jax.lax.pmean(aux, data_axes)
        return y.reshape(bl, s, d), aux

    return inner(x, p["router"], p["w_in"], p["w_out"], w_gate, *norm_args)


def moe_forward(cfg, p, x, *, mesh=None, data_axes=("data",),
                model_axis="model", mode: str = "reference", prenorm=None):
    """Dispatch between implementations (cfg.moe.impl / mesh availability).

    ``mode`` routes the expert FFN through the fused dual-GEMM epilogue
    kernel on *every* implementation: the shard_map paths (ep/tp) run the
    interpret-safe pallas_call under collective tracing behind the
    ``_COLLECTIVE_SAFE_MODES`` capability gate, with full-K policies so
    fused stays bitwise-equal to the reference oracle (DESIGN.md §16).
    ``prenorm`` is the enclosing block's (scale, bias) norm pair — blocks
    hand the pre-norm residual stream here and the shard_map paths norm the
    per-rank token slice inside the shard.
    """
    impl = cfg.moe.impl
    if impl == "auto":
        if (mesh is None or model_axis not in mesh.axis_names
                or mesh.shape[model_axis] == 1):
            impl = "dense"
        elif (cfg.moe.shard == "expert"
              and cfg.moe.num_experts % mesh.shape[model_axis] == 0):
            impl = "ep"
        else:
            impl = "tp"
    if impl == "ep":
        return moe_ep(cfg, p, x, mesh=mesh, data_axes=data_axes,
                      model_axis=model_axis, mode=mode, prenorm=prenorm)
    if impl == "tp":
        return moe_tp(cfg, p, x, mesh=mesh, data_axes=data_axes,
                      model_axis=model_axis, mode=mode, prenorm=prenorm)
    if prenorm is not None:
        x = apply_prenorm(cfg, x, prenorm)
    return moe_dense(cfg, p, x, mode=mode)
