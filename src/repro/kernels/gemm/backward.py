"""Kernel-side fused backward for the GEMM megakernel (DESIGN.md §11).

The forward (DESIGN.md §9-§10) is ``C = epilogue(prologue(A) @ B [, A@B2])``
in one launch. This module is its hand-written chain transpose, run as two
fused Pallas launches instead of the jnp-oracle recompute VJP:

  * **dA launch** — ``dAn = gbar @ Bᵀ [+ gbar2 @ B2ᵀ]`` where the cotangent
    stream ``gbar`` is the *transposed epilogue applied as a prologue on g*:
    act'/gating/scale/rope-adjoint run on each g tile as it streams in,
    consuming the fwd launch's saved preactivations
    (:meth:`Epilogue.transpose_tile`). The store runs the prologue's
    transpose (:meth:`Prologue.transpose`): the norm backward is computed
    tile-wise from the streamed raw-A tile — the normed activation is never
    re-materialized — and the dgamma/dbeta row partials are folded into the
    same store (one partial row per row block; a tiny jnp sum finishes the
    cross-block reduction).
  * **dB launch** — ``dB = Anᵀ @ gbar`` with the norm prologue recomputed on
    the streamed A tiles exactly like the fwd (same full-K rule, same
    precomputed-stats fast path, same MXU-dtype rounding point). The
    dual-GEMM SwiGLU case shares ONE dual-output launch: ``dB`` and ``dB2``
    accumulate side by side from the same A stream, and the dbias
    column-sum is folded into the same store.

dresidual is the identity (g, no launch); dscale and the rope-table
cotangents are tiny jnp reductions over arrays already in HBM
(:meth:`Epilogue.operand_grads`) and are DCE'd when unused.

Both launches resolve their own ``gemm_bwd`` policies through the analytic
autotuner (chain-aware VMEM legality + traffic), pinned to the forward
policy's traversal order so grid swizzling stays a pure scheduling
transform across fwd AND bwd — gradients are bitwise swizzle-invariant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import autotune, tiles
from repro.core.policy import KernelPolicy
from .epilogue import EPILOGUE_NONE, Epilogue
from .prologue import PROLOGUE_NONE, Prologue
from .kernel import (_fit_block, _upcast, epilogue_f32_kwargs,
                     prologue_f32_kwargs)

_F32 = jnp.float32


def _preacts_f32(epilogue: Epilogue, ins: dict) -> tuple:
    p = ins["preact"][...].astype(_F32) if "preact" in ins else None
    p2 = ins["preact2"][...].astype(_F32) if "preact2" in ins else None
    return p, p2


# ---------------------------------------------------------------------------
# dA launch: dAn = gbar @ Bᵀ (+ gbar2 @ B2ᵀ), norm transpose in the store.
# ---------------------------------------------------------------------------

def _da_kernel(*refs, in_names, out_names, n_ctr, epilogue: Epilogue,
               prologue: Prologue, da_dtype):
    ins = dict(zip(in_names, refs[:len(in_names)]))
    outs = dict(zip(out_names, refs[len(in_names):-1]))
    acc_ref = refs[-1]
    ctr = pl.program_id(1)

    @pl.when(ctr == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    streams = epilogue.transpose_tile(
        ins["g"][...].astype(_F32), *_preacts_f32(epilogue, ins),
        **epilogue_f32_kwargs(epilogue, ins))
    # contract the (bm, bctr) cotangent with the (bko, bctr) weight block
    # over the shared N dim — the in-kernel transpose of B
    dims = (((1,), (1,)), ((), ()))
    bt = _upcast(ins["b"][...]).astype(_F32)
    acc_ref[...] += jax.lax.dot_general(streams["g_acc"], bt, dims,
                                        preferred_element_type=_F32)
    if epilogue.gate:
        b2t = _upcast(ins["b2"][...]).astype(_F32)
        acc_ref[...] += jax.lax.dot_general(streams["g_acc2"], b2t, dims,
                                            preferred_element_type=_F32)

    @pl.when(ctr == n_ctr - 1)
    def _store():
        dan = acc_ref[...]
        if prologue.is_identity:
            outs["da"][...] = dan.astype(da_dtype)
        else:
            a32 = _upcast(ins["a"][...]).astype(_F32)
            tr = prologue.transpose(dan, a32,
                                    **prologue_f32_kwargs(prologue, ins))
            outs["da"][...] = tr["da"].astype(da_dtype)
            for name in prologue.grad_names():
                outs[name][...] = tr[name].astype(outs[name].dtype)


@functools.partial(jax.jit, static_argnames=("policy", "epilogue", "prologue",
                                             "interpret"))
def _gemm_bwd_da(a, b, g, extras, preacts, *, policy: KernelPolicy,
                 epilogue: Epilogue, prologue: Prologue, interpret: bool):
    """dA (+ dgamma/dbeta partials, fast-path dmean/drstd) in one launch."""
    m, k = a.shape
    _, n = b.shape
    ops = dict(zip(prologue.operand_names() + epilogue.operand_names(),
                   extras))
    bm = _fit_block(m, policy.block_m, prefer=32)
    # the prologue transpose's row reductions need whole feature rows of
    # dAn, so the output-column block pins to full K (both stats paths)
    bko = k if not prologue.is_identity else \
        _fit_block(k, policy.block_n, prefer=tiles.LANE)
    bctr = _fit_block(n, policy.block_k,
                      epilogue.head_dim if epilogue.rope else 1,
                      prefer=tiles.LANE)
    num_rows, num_cols, n_ctr = m // bm, k // bko, n // bctr
    swizzle = policy.swizzle

    def row_col(i):
        return swizzle.remap(i, num_rows, num_cols)

    def g_map(i, c):
        return (row_col(i)[0], c)

    def b_map(i, c):
        return (row_col(i)[1], c)

    def o_map(i, c):
        return row_col(i)

    def row_map(i, c):
        return (row_col(i)[0], 0)

    def kcol_map(i, c):
        return (0, row_col(i)[1])

    def part_map(i, c):
        r, col = row_col(i)
        return (r, 0, col)

    def ctr_map(i, c):
        return (0, c)

    in_names, in_arrays, in_specs = ["g"], [g], [
        tiles.block_spec((bm, bctr), g_map, g.dtype,
                         allow_ragged_minor=tiles.shape_ragged(m, n, g.dtype))]

    def add(name, arr, blk, imap, ragged=True):
        in_names.append(name)
        in_arrays.append(arr)
        in_specs.append(tiles.block_spec(blk, imap, arr.dtype,
                                         allow_ragged_minor=ragged))

    for i, p in enumerate(preacts):
        add("preact" if i == 0 else "preact2", p, (bm, bctr), g_map,
            tiles.shape_ragged(m, n, p.dtype))
    add("b", b, (bko, bctr), b_map, tiles.shape_ragged(k, n, b.dtype))
    if epilogue.gate:
        add("b2", ops["b2"], (bko, bctr), b_map,
            tiles.shape_ragged(k, n, ops["b2"].dtype))
    if epilogue.bias:
        add("bias", ops["bias"], (1, bctr), ctr_map)
    if epilogue.scale:
        smap = {"row": row_map, "col": ctr_map}.get(
            epilogue.scale_kind, lambda i, c: (0, 0))
        add("scale", ops["scale"], epilogue.scale_block(bm, bctr), smap)
    if epilogue.rope:
        add("sin", ops["sin"], (bm, epilogue.head_dim), row_map)
        add("cos", ops["cos"], (bm, epilogue.head_dim), row_map)
    if not prologue.is_identity:
        add("a", a, (bm, bko), o_map, tiles.shape_ragged(m, k, a.dtype))
        add("gamma", ops["gamma"], (1, bko), kcol_map)
        if prologue.beta:
            add("beta", ops["beta"], (1, bko), kcol_map)
        if prologue.precomputed_stats:
            if prologue.norm == "layernorm":
                add("mean", ops["mean"], (bm, 1), row_map)
            add("rstd", ops["rstd"], (bm, 1), row_map)

    out_names = ["da"]
    out_specs = [tiles.block_spec((bm, bko), o_map, a.dtype,
                                  allow_ragged_minor=tiles.shape_ragged(
                                      m, k, a.dtype))]
    out_shape = [jax.ShapeDtypeStruct((m, k), a.dtype)]
    if not prologue.is_identity:
        for name in prologue.grad_names():
            if name in ("dgamma", "dbeta"):
                # one partial row per (row block, col block); jnp sums them.
                # A (1, K) middle dim keeps the block's last two dims equal
                # to the array's, which the TPU lowering requires.
                out_specs.append(tiles.block_spec((None, 1, bko), part_map,
                                                  _F32,
                                                  allow_ragged_minor=True))
                out_shape.append(jax.ShapeDtypeStruct((num_rows, 1, k), _F32))
            else:  # dmean / drstd: one (rows, 1) column, exact per row block
                out_specs.append(tiles.block_spec((bm, 1), row_map, _F32,
                                                  allow_ragged_minor=True))
                out_shape.append(jax.ShapeDtypeStruct((m, 1), _F32))
            out_names.append(name)

    tiles.check_vmem_budget(
        [(tuple(s.block_shape), arr.dtype)
         for s, arr in zip(in_specs, in_arrays)],
        n_buffers=policy.n_buffers, scratch_bytes=bm * bko * 4,
        what="gemm_bwd_da")
    kernel = functools.partial(_da_kernel, in_names=tuple(in_names),
                               out_names=tuple(out_names), n_ctr=n_ctr,
                               epilogue=epilogue, prologue=prologue,
                               da_dtype=a.dtype)
    results = pl.pallas_call(
        kernel,
        grid=(num_rows * num_cols, n_ctr),
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
        scratch_shapes=[pltpu.VMEM((bm, bko), _F32)],
        compiler_params=tiles.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*in_arrays)
    if len(out_names) == 1:
        return {"da": results}
    return dict(zip(out_names, results))


# ---------------------------------------------------------------------------
# dB launch: dB[, dB2] = Anᵀ @ gbar[, gbar2], dbias folded into the store.
# ---------------------------------------------------------------------------

def _db_kernel(*refs, in_names, out_names, n_ctr, epilogue: Epilogue,
               prologue: Prologue, db_dtype):
    n_scratch = epilogue.n_accumulators + (1 if epilogue.bias else 0)
    ins = dict(zip(in_names, refs[:len(in_names)]))
    outs = dict(zip(out_names, refs[len(in_names):-n_scratch]))
    scratch = refs[-n_scratch:]
    acc_ref = scratch[0]
    acc2_ref = scratch[1] if epilogue.gate else None
    dbias_ref = scratch[-1] if epilogue.bias else None
    ctr = pl.program_id(1)

    @pl.when(ctr == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if epilogue.gate:
            acc2_ref[...] = jnp.zeros_like(acc2_ref)
        if epilogue.bias:
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    a_t = _upcast(ins["a"][...])
    if not prologue.is_identity:
        # tile-wise norm recompute, with the fwd's exact rounding point:
        # normalize in fp32, round through the MXU input dtype, contract
        a_t = prologue.apply(a_t.astype(_F32),
                             **prologue_f32_kwargs(prologue, ins)
                             ).astype(a_t.dtype)
    an = a_t.astype(_F32)
    streams = epilogue.transpose_tile(
        ins["g"][...].astype(_F32), *_preacts_f32(epilogue, ins),
        **epilogue_f32_kwargs(epilogue, ins))
    # contract the (bctr, bko) normed-A tile with the (bctr, bn) cotangent
    # over the shared M dim — the in-kernel transpose of A
    dims = (((0,), (0,)), ((), ()))
    acc_ref[...] += jax.lax.dot_general(an, streams["g_acc"], dims,
                                        preferred_element_type=_F32)
    if epilogue.gate:
        acc2_ref[...] += jax.lax.dot_general(an, streams["g_acc2"], dims,
                                             preferred_element_type=_F32)
    if epilogue.bias:
        dbias_ref[...] += jnp.sum(streams["g_bias"], axis=0, keepdims=True)

    @pl.when(ctr == n_ctr - 1)
    def _store():
        outs["db"][...] = acc_ref[...].astype(db_dtype)
        if epilogue.gate:
            outs["db2"][...] = acc2_ref[...].astype(db_dtype)
        if epilogue.bias:
            outs["dbias"][...] = dbias_ref[...]


@functools.partial(jax.jit, static_argnames=("policy", "epilogue", "prologue",
                                             "interpret"))
def _gemm_bwd_db(a, b, g, extras, preacts, *, policy: KernelPolicy,
                 epilogue: Epilogue, prologue: Prologue, interpret: bool):
    """dB (+ dB2 sharing the launch, + folded dbias) in one launch."""
    m, k = a.shape
    _, n = b.shape
    ops = dict(zip(prologue.operand_names() + epilogue.operand_names(),
                   extras))
    # launch dims: out (K, N), contraction M. The recompute-path norm pins
    # the out-row block to full K (the streamed A tile must span whole
    # feature rows, exactly the fwd rule); the fast path keeps K-blocking.
    bko = k if prologue.needs_full_k else \
        _fit_block(k, policy.block_m, prefer=tiles.LANE)
    bn = _fit_block(n, policy.block_n,
                    epilogue.head_dim if epilogue.rope else 1,
                    prefer=tiles.LANE)
    bctr = _fit_block(m, policy.block_k, prefer=32)
    num_rows, num_cols, n_ctr = k // bko, n // bn, m // bctr
    swizzle = policy.swizzle

    def row_col(i):
        return swizzle.remap(i, num_rows, num_cols)

    def a_map(i, c):
        return (c, row_col(i)[0])

    def g_map(i, c):
        return (c, row_col(i)[1])

    def o_map(i, c):
        return row_col(i)

    def krow_map(i, c):
        return (0, row_col(i)[0])

    def col_map(i, c):
        return (0, row_col(i)[1])

    def ctr_map(i, c):
        return (c, 0)

    in_names, in_arrays, in_specs = ["a"], [a], [
        tiles.block_spec((bctr, bko), a_map, a.dtype,
                         allow_ragged_minor=tiles.shape_ragged(m, k, a.dtype))]

    def add(name, arr, blk, imap, ragged=True):
        in_names.append(name)
        in_arrays.append(arr)
        in_specs.append(tiles.block_spec(blk, imap, arr.dtype,
                                         allow_ragged_minor=ragged))

    if not prologue.is_identity:
        add("gamma", ops["gamma"], (1, bko), krow_map)
        if prologue.beta:
            add("beta", ops["beta"], (1, bko), krow_map)
        if prologue.precomputed_stats:
            if prologue.norm == "layernorm":
                add("mean", ops["mean"], (bctr, 1), ctr_map)
            add("rstd", ops["rstd"], (bctr, 1), ctr_map)
    add("g", g, (bctr, bn), g_map, tiles.shape_ragged(m, n, g.dtype))
    for i, p in enumerate(preacts):
        add("preact" if i == 0 else "preact2", p, (bctr, bn), g_map,
            tiles.shape_ragged(m, n, p.dtype))
    if epilogue.bias:
        add("bias", ops["bias"], (1, bn), col_map)
    if epilogue.scale:
        smap = {"row": ctr_map, "col": col_map}.get(
            epilogue.scale_kind, lambda i, c: (0, 0))
        add("scale", ops["scale"], epilogue.scale_block(bctr, bn), smap)
    if epilogue.rope:
        add("sin", ops["sin"], (bctr, epilogue.head_dim), ctr_map)
        add("cos", ops["cos"], (bctr, epilogue.head_dim), ctr_map)

    out_names = ["db"]
    out_specs = [tiles.block_spec((bko, bn), o_map, b.dtype,
                                  allow_ragged_minor=tiles.shape_ragged(
                                      k, n, b.dtype))]
    out_shape = [jax.ShapeDtypeStruct((k, n), b.dtype)]
    if epilogue.gate:
        out_names.append("db2")
        out_specs.append(tiles.block_spec((bko, bn), o_map, b.dtype,
                                          allow_ragged_minor=tiles.shape_ragged(
                                              k, n, b.dtype)))
        out_shape.append(jax.ShapeDtypeStruct((k, n), b.dtype))
    if epilogue.bias:
        # every out-row block accumulates the same column sum; the store is
        # idempotent across them (last writer wins with identical values)
        out_names.append("dbias")
        out_specs.append(tiles.block_spec((1, bn), col_map, _F32,
                                          allow_ragged_minor=True))
        out_shape.append(jax.ShapeDtypeStruct((1, n), _F32))

    n_acc = epilogue.n_accumulators
    scratch = [pltpu.VMEM((bko, bn), _F32) for _ in range(n_acc)]
    if epilogue.bias:
        scratch.append(pltpu.VMEM((1, bn), _F32))
    tiles.check_vmem_budget(
        [(tuple(s.block_shape), arr.dtype)
         for s, arr in zip(in_specs, in_arrays)],
        n_buffers=policy.n_buffers, scratch_bytes=n_acc * bko * bn * 4,
        what="gemm_bwd_db")
    kernel = functools.partial(_db_kernel, in_names=tuple(in_names),
                               out_names=tuple(out_names), n_ctr=n_ctr,
                               epilogue=epilogue, prologue=prologue,
                               db_dtype=b.dtype)
    results = pl.pallas_call(
        kernel,
        grid=(num_rows * num_cols, n_ctr),
        in_specs=in_specs,
        out_specs=out_specs if len(out_specs) > 1 else out_specs[0],
        out_shape=out_shape if len(out_shape) > 1 else out_shape[0],
        scratch_shapes=scratch,
        compiler_params=tiles.compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*in_arrays)
    if len(out_names) == 1:
        return {"db": results}
    return dict(zip(out_names, results))


# ---------------------------------------------------------------------------
# Orchestration: the custom-VJP backward body.
# ---------------------------------------------------------------------------

def resolve_bwd_policies(fwd_policy: KernelPolicy, m: int, n: int, k: int,
                         dtype, epilogue: Epilogue,
                         prologue: Prologue) -> tuple:
    """The (dA, dB) launch policies for a fwd launch: resolved through the
    memoized autotuner under the ``gemm_bwd`` op kind (chain-aware VMEM
    legality + bwd traffic model), with the traversal order pinned to the
    fwd policy's swizzle so the whole fwd+bwd step shares one grid-order
    decision (and gradients stay bitwise swizzle-invariant)."""
    da = autotune.select_policy("gemm_bwd", (m, k, n), str(dtype),
                                epilogue=epilogue, prologue=prologue,
                                variant="da", swizzle=fwd_policy.swizzle)
    db = autotune.select_policy("gemm_bwd", (k, n, m), str(dtype),
                                epilogue=epilogue, prologue=prologue,
                                variant="db", swizzle=fwd_policy.swizzle)
    return da, db


def bwd_policies_available(fwd_policy: KernelPolicy, m: int, n: int, k: int,
                           dtype, epilogue: Epilogue,
                           prologue: Prologue) -> bool:
    """True iff the kernel backward can run for this launch shape. The
    differentiated fwd consults this (deterministic — the memoized probe is
    the same resolution the bwd will do) so it never stores preactivations
    the oracle-fallback VJP would ignore."""
    try:
        resolve_bwd_policies(fwd_policy, m, n, k, dtype, epilogue, prologue)
    except ValueError:
        return False
    return True


def gemm_fused_bwd(a, b, extras, preacts, out, g, *, policy: KernelPolicy,
                   epilogue: Epilogue = EPILOGUE_NONE,
                   prologue: Prologue = PROLOGUE_NONE,
                   interpret: bool = True, policies=None) -> tuple:
    """Run the fused backward: returns ``(da, db, dextras)`` matching the
    fwd's ``(a, b, extras)`` — both bwd GEMMs as fused Pallas launches, the
    remaining operand cotangents as tiny jnp reductions.

    ``policies`` lets the caller pass pre-resolved (dA, dB) policies so the
    legality probe (the only sanctioned fallback point — ops.py catches
    *its* ValueError, not launch errors) happens exactly once.
    """
    m, k = a.shape
    _, n = b.shape
    names = prologue.operand_names() + epilogue.operand_names()
    ops = dict(zip(names, extras))
    da_pol, db_pol = policies if policies is not None else \
        resolve_bwd_policies(policy, m, n, k, a.dtype, epilogue, prologue)
    if obs.enabled():
        # journaled at the dispatch site (the launches themselves are jitted
        # wrappers) — one event per bwd GEMM, same semantics the old
        # monkeypatch counters had
        db_bytes = jnp.dtype(a.dtype).itemsize
        chain = f"{prologue.describe()}|{epilogue.describe()}"
        obs.launch("gemm_bwd_da", variant="da", policy=da_pol, chain=chain,
                   dma_bytes=autotune.gemm_bwd_traffic_bytes(
                       da_pol, m, k, n, db_bytes, "da"),
                   flops=2 * m * n * k)
        obs.launch("gemm_bwd_db", variant="db", policy=db_pol, chain=chain,
                   dma_bytes=autotune.gemm_bwd_traffic_bytes(
                       db_pol, k, n, m, db_bytes, "db"),
                   flops=(2 if epilogue.gate else 1) * 2 * m * n * k)
    da_out = _gemm_bwd_da(a, b, g, extras, preacts, policy=da_pol,
                          epilogue=epilogue, prologue=prologue,
                          interpret=interpret)
    db_out = _gemm_bwd_db(a, b, g, extras, preacts, policy=db_pol,
                          epilogue=epilogue, prologue=prologue,
                          interpret=interpret)

    # jnp half of the transpose rule — only dscale and the rope-table
    # cotangents need it (dbias is folded into the dB store, dresidual is
    # the identity); unused entries are DCE'd under jit anyway
    og = {}
    if epilogue.scale or epilogue.rope:
        f32 = [None if p is None else p.astype(_F32)
               for p in (list(preacts) + [None, None])[:2]]
        ekw = {}
        if epilogue.bias:
            ekw["bias"] = ops["bias"].astype(_F32)
        if epilogue.scale:
            ekw["scale"] = ops["scale"].astype(_F32)
        if epilogue.rope:
            ekw["sin"] = ops["sin"].astype(_F32)
            ekw["cos"] = ops["cos"].astype(_F32)
        og = epilogue.operand_grads(
            g.astype(_F32), f32[0], f32[1],
            None if out is None else out.astype(_F32), **ekw, residual=None)

    dextras = []
    for name in names:
        op = ops[name]
        if name == "gamma":
            grad = jnp.sum(da_out["dgamma"], axis=0, keepdims=True)
        elif name == "beta":
            grad = jnp.sum(da_out["dbeta"], axis=0, keepdims=True)
        elif name == "mean":
            grad = da_out["dmean"]
        elif name == "rstd":
            grad = da_out["drstd"]
        elif name == "b2":
            grad = db_out["db2"]
        elif name == "bias":
            grad = db_out["dbias"]
        elif name == "residual":
            grad = g
        else:  # scale / sin / cos: the jnp reduction half
            grad = og[name]
        dextras.append(jnp.asarray(grad).reshape(op.shape).astype(op.dtype))
    return da_out["da"], db_out["db"], tuple(dextras)
