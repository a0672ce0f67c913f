"""Public GEMM ops: policy-aware dispatch with a reference path.

``mode``:
  * "reference"        — jnp (used by the 512-device dry-run; XLA fuses)
  * "pallas_interpret" — the Pallas kernel, interpret=True (CPU validation)
  * "pallas_tpu"       — the Pallas kernel lowered for real TPUs

Policy resolution order (DESIGN.md §5): explicit ``policy`` > legacy
``schedule``/``swizzle`` keywords (deprecation shim) > the analytic autotuner
(``autotune.select_policy``, memoized per shape-bucket).

:func:`gemm_fused` is the megakernel entry point (DESIGN.md §9-§10): one
GEMM launch whose A tiles run a declarative :class:`Prologue`
(rmsnorm/layernorm as the operand streams in — producers never write the
normed activation) and whose store runs a declarative :class:`Epilogue`
chain — bias, activation, dual-output SwiGLU gating, residual add, fp8
dequant scale, and the QKV→RoPE rotation — so consumers never re-read the
activation from HBM.
"""
from __future__ import annotations

import contextlib
import functools
import warnings

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import autotune
from repro.core.grid_swizzle import SwizzleConfig, ROW_MAJOR, best_window
from repro.core.policy import KernelPolicy, make_policy
from repro.core.schedule import Schedule
from repro.kernels.modes import interpret_for
from .epilogue import EPILOGUE_NONE, Epilogue
from .prologue import PROLOGUE_NONE, Prologue
from .kernel import _fit_block, _gemm_pallas, gemm_pallas
from .ref import gemm_fused_ref, gemm_ref

_DEPRECATION_MSG = (
    "gemm: the schedule=/swizzle= keywords are deprecated; pass "
    "policy=KernelPolicy(...) (or neither, to use the autotuner)")


def _policy_from_schedule(schedule: Schedule, swizzle, m, n, k,
                          dtype) -> KernelPolicy:
    """Deprecation shim: fit a legacy Schedule's blocks to the problem and
    wrap them (plus the requested/auto swizzle) in an explicit policy."""
    warnings.warn(_DEPRECATION_MSG, DeprecationWarning, stacklevel=3)
    bm = _fit_block(m, schedule.block_m, prefer=128)
    bn = _fit_block(n, schedule.block_n, prefer=128)
    bk = _fit_block(k, schedule.block_k, prefer=128)
    if swizzle == "auto":
        num_rows, num_cols = max(1, m // bm), max(1, n // bn)
        itemsize = jnp.dtype(dtype).itemsize
        swizzle = best_window(num_rows, num_cols, bm * k * itemsize,
                              k * bn * itemsize,
                              candidates=(1, 2, 4, 8, num_rows))
    elif swizzle is None:
        swizzle = ROW_MAJOR
    return make_policy("gemm", block_m=bm, block_n=bn, block_k=bk,
                       n_buffers=schedule.n_buffers, swizzle=swizzle,
                       name=f"shim_{schedule.name}")


def _policy_from_swizzle(swizzle, m, n, k, dtype) -> KernelPolicy:
    """Deprecation shim for swizzle-only legacy calls: rank the autotuner's
    candidate set restricted to the requested traversal order, instead of
    pinning the old hard-coded pingpong-512 schedule (which silently leaned
    on the _fit_policy clamp for every small-M/N/K problem)."""
    warnings.warn(_DEPRECATION_MSG, DeprecationWarning, stacklevel=3)
    return autotune.select_policy(
        "gemm", (m, n, k), str(dtype),
        swizzle=swizzle if swizzle is not None else ROW_MAJOR)


def gemm(a, b, *, policy: KernelPolicy | None = None,
         schedule: Schedule | None = None,
         swizzle: SwizzleConfig | str | None = "auto",
         out_dtype=jnp.bfloat16, mode: str = "pallas_interpret"):
    interpret = interpret_for(mode)
    if mode == "reference":
        return gemm_ref(a, b, out_dtype)
    m, k = a.shape
    _, n = b.shape
    if policy is None:
        if schedule is not None:
            # legacy keyword surface -> explicit policy (deprecation shim)
            policy = _policy_from_schedule(schedule, swizzle, m, n, k,
                                           a.dtype)
        elif isinstance(swizzle, SwizzleConfig) or swizzle is None:
            # swizzle-only legacy surface -> autotuned blocks under the
            # requested traversal order
            policy = _policy_from_swizzle(swizzle, m, n, k, a.dtype)
        else:
            policy = autotune.select_policy("gemm", (m, n, k), str(a.dtype))
    if obs.enabled():
        obs.launch("gemm",
                   grid=(max(1, m // policy.block_m),
                         max(1, n // policy.block_n)),
                   policy=policy, flops=2 * m * n * k,
                   dma_bytes=autotune.gemm_traffic_bytes(
                       policy, m, n, k, jnp.dtype(a.dtype).itemsize))
    return gemm_pallas(a, b, policy=policy, out_dtype=out_dtype,
                       interpret=interpret)


# Default backward path for gemm_fused (DESIGN.md §11): 'kernel' runs the
# hand-written chain transpose as fused Pallas launches; 'reference' keeps
# the jnp-oracle recompute VJP as the grad oracle.
BWD_MODES = ("kernel", "reference", "auto")
_DEFAULT_BWD_MODE = ["kernel"]


@contextlib.contextmanager
def default_bwd_mode(mode: str):
    """Temporarily override the backward path used by gemm_fused calls that
    don't pass ``bwd_mode`` (i.e. every model layer) — the lever the parity
    tests and benchmarks use to pit the kernel-side fused backward against
    the oracle-recompute VJP on identical graphs."""
    if mode not in BWD_MODES:
        raise ValueError(f"unknown bwd_mode {mode!r}; have {BWD_MODES}")
    prev = _DEFAULT_BWD_MODE[0]
    _DEFAULT_BWD_MODE[0] = mode
    try:
        yield
    finally:
        _DEFAULT_BWD_MODE[0] = prev


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _gemm_fused(policy, out_dtype, interpret, epilogue, prologue, bwd_mode,
                a, b, extras):
    return _gemm_pallas(a, b, *extras, policy=policy, out_dtype=out_dtype,
                        interpret=interpret, epilogue=epilogue,
                        prologue=prologue)


def _gemm_fused_fwd(policy, out_dtype, interpret, epilogue, prologue,
                    bwd_mode, a, b, extras):
    """Differentiated fwd: under the kernel bwd path the launch additionally
    stores the raw accumulator(s) the chain transpose needs (rounded through
    the MXU input dtype — see Epilogue.needs_saved_preact), and the output
    rides the residuals when the rope-table cotangents must invert the
    rotation from it. When no legal gemm_bwd policy exists for this shape
    (the bwd will fall back to the oracle VJP), nothing extra is stored."""
    save = bwd_mode == "kernel" and epilogue.saved_accumulators > 0
    if save:
        from . import backward

        m, k = a.shape
        n = b.shape[1]
        save = backward.bwd_policies_available(policy, m, n, k, a.dtype,
                                               epilogue, prologue)
    if save:
        out, *preacts = _gemm_pallas(a, b, *extras, policy=policy,
                                     out_dtype=out_dtype,
                                     interpret=interpret, epilogue=epilogue,
                                     prologue=prologue, save_preact=True)
    else:
        out = _gemm_pallas(a, b, *extras, policy=policy, out_dtype=out_dtype,
                           interpret=interpret, epilogue=epilogue,
                           prologue=prologue)
        preacts = []
    keep_out = out if (bwd_mode == "kernel" and epilogue.rope) else None
    return out, (a, b, extras, tuple(preacts), keep_out)


def _gemm_fused_bwd(policy, out_dtype, interpret, epilogue, prologue,
                    bwd_mode, res, g):
    """Backward dispatch (DESIGN.md §11).

    'kernel' (default): the hand-written chain transpose — dA = gbar@Bᵀ and
    dB = Anᵀ@gbar run as fused Pallas launches with the transposed epilogue
    applied to the g tiles as they stream in and the norm prologue
    recomputed tile-wise (kernels/gemm/backward.py).

    'reference': autodiff of the unfused jnp oracle (forward recompute,
    remat-style) — kept as the grad oracle the kernel path is tested
    against, and as the remat-friendly fallback.
    """
    a, b, extras, preacts, out = res

    def oracle_vjp():
        names = prologue.operand_names() + epilogue.operand_names()

        def ref_fn(a, b, extras):
            kw = dict(zip(names, extras))
            return gemm_fused_ref(a, b, epilogue=epilogue, prologue=prologue,
                                  out_dtype=out_dtype, **kw)

        _, vjp = jax.vjp(ref_fn, a, b, extras)
        return vjp(g)

    if bwd_mode == "reference":
        return oracle_vjp()
    from . import backward

    m, k = a.shape
    n = b.shape[1]
    try:
        policies = backward.resolve_bwd_policies(policy, m, n, k, a.dtype,
                                                 epilogue, prologue)
    except ValueError:
        # no VMEM-legal gemm_bwd policy for this shape (e.g. the norm
        # transpose's full-K tiles at huge feature dims) — the same
        # legality signal the fwd fusion ladder falls back on. The bwd
        # must handle every shape the fwd legally engaged, so fall back
        # to the oracle-recompute VJP (raised at trace time only). The
        # catch is deliberately narrow: errors from the launches
        # themselves are bugs and must surface, not reroute silently.
        return oracle_vjp()
    return backward.gemm_fused_bwd(a, b, extras, preacts, out, g,
                                   policy=policy, epilogue=epilogue,
                                   prologue=prologue, interpret=interpret,
                                   policies=policies)


_gemm_fused.defvjp(_gemm_fused_fwd, _gemm_fused_bwd)


def gemm_fused(a, b, *, epilogue: Epilogue = EPILOGUE_NONE,
               prologue: Prologue = PROLOGUE_NONE, b2=None, bias=None,
               residual=None, scale=None, sin=None, cos=None,
               gamma=None, beta=None, mean=None, rstd=None,
               policy: KernelPolicy | None = None,
               out_dtype=jnp.bfloat16, mode: str = "pallas_interpret",
               bwd_mode: str | None = None):
    """C = epilogue(prologue(A) @ B) in one kernel launch (DESIGN.md §9-§10).

    Extra operands per epilogue flag: ``gate`` → ``b2`` (K, N) second weight
    (dual-output SwiGLU GEMM, C = act(A@B) * (A@B2)); ``bias`` → (N,);
    ``residual`` → (M, N); ``scale`` → scalar, (M, 1) row or (1, N) column
    per ``scale_kind`` (fp8 dequant — per-tensor or per-channel — and the
    residual scale); ``rope`` → ``sin``/``cos`` (M, head_dim)
    duplicated-halves tables (the fused QKV→RoPE rotation).

    ``bwd_mode`` picks the ``jax.grad`` path (DESIGN.md §11): ``"kernel"``
    (the default, overridable via :func:`default_bwd_mode`) runs the
    hand-written chain transpose as fused Pallas launches — both bwd GEMMs
    with the transposed epilogue as a prologue on g and the norm recomputed
    tile-wise; ``"reference"`` keeps the jnp-oracle recompute VJP (the grad
    oracle); ``"auto"`` routes per shape bucket via
    ``autotune.select_bwd_mode`` (docs/autotuning.md) — kernel on
    train-shaped cells, oracle on degenerate ones. 'reference' *mode*
    always differentiates the oracle directly.

    Per prologue flag: any norm → ``gamma`` (K,) row scale; ``beta`` →
    (K,) layernorm bias row; ``precomputed_stats`` → ``rstd`` (M,) (and
    ``mean`` (M,) for layernorm) f32 row statistics (the fast path that
    keeps K-blocking; see Prologue.compute_stats).

    'reference' mode runs the unfused jnp oracle (full HBM round trips);
    the pallas modes run the prologue on each A tile as it streams in and
    the epilogue inside the kernel's final store. With ``policy=None`` the
    autotuner resolves a chain-aware policy (extra operands and the second
    accumulator count against the VMEM budget; a recompute-path norm
    prologue pins block_k to the full feature dim).
    """
    provided = dict(b2=b2, bias=bias, residual=residual, scale=scale,
                    sin=sin, cos=cos)
    pro_provided = dict(gamma=gamma, beta=beta, mean=mean, rstd=rstd)
    wanted = epilogue.operand_names()
    pro_wanted = prologue.operand_names()
    for name, val in provided.items():
        if (val is not None) != (name in wanted):
            raise ValueError(
                f"gemm_fused: operand {name!r} "
                f"{'missing for' if name in wanted else 'not accepted by'} "
                f"epilogue {epilogue.describe()!r}")
    for name, val in pro_provided.items():
        if (val is not None) != (name in pro_wanted):
            raise ValueError(
                f"gemm_fused: operand {name!r} "
                f"{'missing for' if name in pro_wanted else 'not accepted by'} "
                f"prologue {prologue.describe()!r}")
    interpret = interpret_for(mode)
    if mode == "reference":
        return gemm_fused_ref(a, b, epilogue=epilogue, prologue=prologue,
                              b2=b2, bias=bias, residual=residual,
                              scale=scale, sin=sin, cos=cos, gamma=gamma,
                              beta=beta, mean=mean, rstd=rstd,
                              out_dtype=out_dtype)
    m, k = a.shape
    _, n = b.shape
    if policy is None:
        policy = autotune.select_policy("gemm", (m, n, k), str(a.dtype),
                                        epilogue=epilogue, prologue=prologue)
    else:
        # two sources of truth: the explicit chain arguments must match the
        # chains the policy's legality/traffic accounting was done for
        if policy.epilogue is not None and policy.epilogue != epilogue:
            raise ValueError(
                f"gemm_fused: policy carries epilogue "
                f"{policy.epilogue.describe()!r} but the call passes "
                f"{epilogue.describe()!r}")
        if policy.prologue is not None and policy.prologue != prologue:
            raise ValueError(
                f"gemm_fused: policy carries prologue "
                f"{policy.prologue.describe()!r} but the call passes "
                f"{prologue.describe()!r}")
    extras = []
    for name in pro_wanted:
        val = pro_provided[name]
        if name in ("gamma", "beta"):
            val = jnp.asarray(val).reshape(1, -1)
        else:  # mean / rstd: (M, 1) f32 columns
            val = jnp.asarray(val, jnp.float32).reshape(-1, 1)
        extras.append(val)
    for name in wanted:
        val = provided[name]
        if name == "bias":
            val = jnp.asarray(val).reshape(1, -1)
        elif name == "scale":
            val = jnp.asarray(val, jnp.float32)
            if epilogue.scale_kind == "row":
                val = val.reshape(-1, 1)    # (M, 1) per-row dequant
            elif epilogue.scale_kind == "col":
                val = val.reshape(1, -1)    # (1, N) per-channel dequant
            else:
                val = val.reshape(1, 1)
        extras.append(val)
    if bwd_mode is None:
        bwd_mode = _DEFAULT_BWD_MODE[0]
    if bwd_mode not in BWD_MODES:
        raise ValueError(f"unknown bwd_mode {bwd_mode!r}; have {BWD_MODES}")
    if bwd_mode == "auto":
        # plan-aware routing (DESIGN.md §15): the roofline + peak-memory
        # model sends degenerate cells (tiny-K: saved preacts dominate) to
        # the oracle VJP and train-shaped cells to the fused kernel bwd.
        # Journaled as a 'bwd_route' plan decision, memoized per bucket.
        bwd_mode = autotune.select_bwd_mode(m, n, k, dtype=str(a.dtype),
                                            epilogue=epilogue,
                                            prologue=prologue)
    out = _gemm_fused(policy, out_dtype, interpret,
                      epilogue, prologue, bwd_mode, a, b, tuple(extras))
    if obs.enabled():
        obs.launch("gemm_fused", variant=bwd_mode,
                   grid=(max(1, m // policy.block_m),
                         max(1, n // policy.block_n)),
                   policy=policy,
                   chain=f"{prologue.describe()}|{epilogue.describe()}",
                   dma_bytes=autotune.gemm_traffic_bytes(
                       policy, m, n, k, jnp.dtype(a.dtype).itemsize),
                   flops=(2 if epilogue.gate else 1) * 2 * m * n * k)
    return out
