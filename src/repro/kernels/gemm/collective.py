"""Ring-overlapped collective GEMM (DESIGN.md §16).

The paper's core async-worker pattern — DMA workers stream the next tile
while MMA workers consume the current one — lifted one level up: ``ppermute``
ring hops stream the next operand chunk between ranks while fused
``gemm_fused`` panel launches consume the chunk already resident. Two
variants, matching the two Megatron TP collectives:

* ``all_gather``      row-parallel A: each rank holds an (m_loc, K) row
  block and the full B. The ring rotates the row blocks; at every step each
  rank GEMMs the block it currently holds into the matching output panel.
  After S steps every rank has the full (M, N) product — the all_gather
  never materializes the gathered A in HBM.
* ``reduce_scatter``  contraction-parallel A/B: each rank holds (M, k_loc)
  and (k_loc, N) and owes a partial product. The fp32 panel accumulator
  rides the ring; at step s each rank computes its contribution to panel
  ``(rank - step - 1) % S`` and adds it to the accumulator it just
  received, so panel p collects contributions in the fixed rank order
  p+1, p+2, ..., p — deterministic, unlike ``psum_scatter``.

Bitwise parity (the kernel's oracle contract): every panel GEMM runs a
full-K policy (block_k == K), which makes each output element a single-tile
dot. XLA's dot is not row-separable — the value of a row can depend on how
many rows share the call — so the jnp paths (reference mode, the oracle)
compute every product as the same ring-panel-shaped dots the ring runs.
The unfused gather-then-gemm path and the jnp oracle therefore match the
ring *bitwise*, per rank, in every mode.

These functions run INSIDE shard_map (they use ``jax.lax.axis_index`` /
``ppermute``); :func:`gemm_collective_sharded` is the host-level wrapper
that builds the shard_map with the right specs.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro import obs
from repro.core import autotune

VARIANTS = ("all_gather", "reduce_scatter")


def _full_k_policy(m, n, k, dtype):
    """Full-K gemm policy (block_k == K): the bitwise-safety pin — K-tile
    accumulation order is the only fp difference vs jnp.dot, so a single K
    tile makes panel GEMMs exact row panels of the full product."""
    pol = autotune.select_policy("gemm", (m, n, k), dtype)
    if pol.block_k == k:
        return pol
    pinned = dataclasses.replace(
        pol, schedule=dataclasses.replace(pol.schedule, block_k=k))
    if not pinned.is_legal():
        raise ValueError(
            f"gemm_collective: no VMEM-legal full-K policy for "
            f"({m}, {n}, {k}) {dtype} — bitwise parity cannot be pinned")
    return pinned


def _jnp_panels(a, b, rows, out_dtype):
    """``a @ b`` as dots of ``rows``-row panels: the one dot shape every
    jnp path shares with the ring, so their results agree bitwise."""
    pet = jnp.float32 if out_dtype == jnp.float32 else None
    return jnp.concatenate(
        [jnp.dot(a[i:i + rows], b, preferred_element_type=pet)
         for i in range(0, a.shape[0], rows)]).astype(out_dtype)


def _panel_gemm(a, b, *, mode, out_dtype, policy, rows):
    """One panel launch: gemm_fused with the pinned policy, or the jnp
    oracle in reference mode (identical values — that is the point).
    ``rows`` is the ring's panel height."""
    if mode == "reference":
        return _jnp_panels(a, b, rows, out_dtype)
    from .ops import gemm_fused
    from .epilogue import EPILOGUE_NONE

    return gemm_fused(a, b, epilogue=EPILOGUE_NONE, policy=policy,
                      out_dtype=out_dtype, mode=mode)


def _ring_perm(axis_size: int):
    return [(j, (j + 1) % axis_size) for j in range(axis_size)]


# ---------------------------------------------------------------------------
# all_gather variant: row-parallel A, ring rotates the row blocks
# ---------------------------------------------------------------------------

def _ag_ring(x, w, *, axis_name, axis_size, mode, out_dtype, policy):
    """x: (m_loc, K) local rows; w: (K, N) full. Returns the full (M, N)
    product on every rank. At step s the chunk a rank holds originated at
    rank (rank - s) % S."""
    s_ = axis_size
    m_loc, k = x.shape
    n = w.shape[1]
    rank = jax.lax.axis_index(axis_name)
    out = jnp.zeros((s_ * m_loc, n), out_dtype)
    chunk = x
    for step in range(s_):
        origin = (rank - step) % s_
        y = _panel_gemm(chunk, w, mode=mode, out_dtype=out_dtype,
                        policy=policy, rows=m_loc)
        out = jax.lax.dynamic_update_slice(out, y, (origin * m_loc, 0))
        if step < s_ - 1:
            chunk = jax.lax.ppermute(chunk, axis_name, _ring_perm(s_))
    return out


def _ag_gather_then_gemm(x, w, *, axis_name, axis_size, mode, out_dtype,
                         policy):
    """Unfused baseline: materialize the gathered A, one big GEMM. The
    full-K policy makes its row panels bitwise-equal to the ring's."""
    del axis_size
    ag = jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
    return _panel_gemm(ag, w, mode=mode, out_dtype=out_dtype, policy=policy,
                       rows=x.shape[0])


# ---------------------------------------------------------------------------
# reduce_scatter variant: contraction-parallel, fp32 accumulator rides the
# ring; panel p sums contributions in rank order p+1, p+2, ..., p
# ---------------------------------------------------------------------------

def _rs_panel(x, p_idx, m_loc):
    return jax.lax.dynamic_slice_in_dim(x, p_idx * m_loc, m_loc, axis=0)


def _rs_ring(x, w, *, axis_name, axis_size, mode, out_dtype, policy):
    """x: (M, k_loc); w: (k_loc, N). Returns this rank's (M/S, N) panel of
    the summed product, accumulated in fp32 in the fixed ring order."""
    s_ = axis_size
    m, _ = x.shape
    m_loc = m // s_
    rank = jax.lax.axis_index(axis_name)
    acc = None
    for step in range(s_):
        p_idx = (rank - step - 1) % s_
        y = _panel_gemm(_rs_panel(x, p_idx, m_loc), w, mode=mode,
                        out_dtype=jnp.float32, policy=policy, rows=m_loc)
        if acc is None:
            acc = y
        else:
            acc = jax.lax.ppermute(acc, axis_name, _ring_perm(s_)) + y
    return acc.astype(out_dtype)


def _rs_gather_then_sum(x, w, *, axis_name, axis_size, mode, out_dtype,
                        policy):
    """Unfused baseline: full partial product per rank, all_gather the
    partial panels, then sum this rank's panel in the SAME rank order the
    ring uses (p+1, p+2, ..., p) — order-matched so the paths stay bitwise.
    ``psum_scatter`` would be one op but its addition order is XLA's."""
    s_ = axis_size
    m, _ = x.shape
    m_loc = m // s_
    rank = jax.lax.axis_index(axis_name)
    partial = _panel_gemm(x, w, mode=mode, out_dtype=jnp.float32,
                          policy=policy, rows=m_loc)
    all_p = jax.lax.all_gather(partial, axis_name, axis=0)  # (S, M, N)
    acc = jnp.zeros((m_loc, w.shape[1]), jnp.float32)
    for i in range(s_):
        src = (rank + 1 + i) % s_
        contrib = jax.lax.dynamic_index_in_dim(all_p, src, 0,
                                               keepdims=False)
        acc = acc + _rs_panel(contrib, rank, m_loc)
    return acc.astype(out_dtype)


def gemm_collective(x, w, *, axis_name: str, axis_size: int, variant: str,
                    mode: str = "pallas_interpret", out_dtype=None,
                    shard=None, plan: str | None = None):
    """Collective GEMM, called inside shard_map (DESIGN.md §16).

    ``variant`` picks the collective ('all_gather' | 'reduce_scatter');
    ``plan`` forces 'ring' (overlapped) or 'gather' (unfused baseline), or
    None to consult ``select_fusion('gemm_collective', ...)`` with the
    interconnect chain term — journaled like every other fusion verdict.
    ``shard`` is the enclosing ShardSpec (memo-key dimension; required when
    ``plan`` is None). Both plans are bitwise-equal by construction.
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; have {VARIANTS}")
    out_dtype = out_dtype or x.dtype
    if variant == "all_gather":
        m_loc, k = x.shape
        n = w.shape[1]
        m = m_loc * axis_size
        pol_shape = (m_loc, n, k)
    else:
        m, k_loc = x.shape
        n = w.shape[1]
        if m % axis_size:
            raise ValueError(
                f"reduce_scatter rows {m} not divisible by ring size "
                f"{axis_size}")
        pol_shape = (m // axis_size, n, k_loc)
        k = k_loc * axis_size
    if plan is None:
        if shard is None:
            raise ValueError("gemm_collective: plan=None requires shard=")
        verdict = autotune.select_fusion("gemm_collective", (m, n, k),
                                         str(x.dtype), shard=shard)
        plan = "ring" if verdict["plan"] == "fused" else "gather"
    policy = (None if mode == "reference"
              else _full_k_policy(*pol_shape, str(x.dtype)))
    fn = {("all_gather", "ring"): _ag_ring,
          ("all_gather", "gather"): _ag_gather_then_gemm,
          ("reduce_scatter", "ring"): _rs_ring,
          ("reduce_scatter", "gather"): _rs_gather_then_sum}[(variant, plan)]
    obs.incr(f"gemm_collective.{variant}.{plan}")
    return fn(x, w, axis_name=axis_name, axis_size=axis_size, mode=mode,
              out_dtype=out_dtype, policy=policy)


def gemm_collective_oracle(x_full, w_full, *, variant: str, axis_size: int,
                           out_dtype=None):
    """Single-host jnp oracle on the UNSHARDED operands. all_gather: the
    plain product, replicated. reduce_scatter: per-rank panels summed over
    the k_loc contributions in the ring's rank order (rank-dependent, so
    the oracle returns the (S, M/S, N) stack of per-rank panels)."""
    out_dtype = out_dtype or x_full.dtype
    m, k = x_full.shape
    n = w_full.shape[1]
    s_ = axis_size
    m_loc, k_loc = m // s_, k // s_
    if variant == "all_gather":
        return _jnp_panels(x_full, w_full, m_loc, out_dtype)
    # per-source partial products, fp32
    parts = [_jnp_panels(x_full[:, src * k_loc:(src + 1) * k_loc],
                         w_full[src * k_loc:(src + 1) * k_loc, :], m_loc,
                         jnp.float32)
             for src in range(s_)]
    panels = []
    for rank in range(s_):
        acc = jnp.zeros((m_loc, n), jnp.float32)
        for i in range(s_):
            src = (rank + 1 + i) % s_
            acc = acc + parts[src][rank * m_loc:(rank + 1) * m_loc, :]
        panels.append(acc.astype(out_dtype))
    return jnp.stack(panels)


def gemm_collective_sharded(x, w, *, mesh, axis: str = "model",
                            variant: str = "all_gather",
                            mode: str = "pallas_interpret",
                            out_dtype=None, plan: str | None = None):
    """Host-level wrapper: shard_map with the specs each variant implies.

    all_gather: x rows over ``axis``, w replicated → full (M, N) replicated.
    reduce_scatter: x cols / w rows over ``axis`` → (M, N) rows over axis.
    """
    from repro.distributed.sharding import ShardSpec

    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; have {VARIANTS}")
    s_ = int(mesh.shape[axis])
    shard = ShardSpec.for_axis(mesh, axis, dim="rows" if
                               variant == "all_gather" else "contract",
                               collective=variant)
    if variant == "all_gather":
        in_specs = (P(axis, None), P(None, None))
        out_specs = P(None, None)
    else:
        in_specs = (P(None, axis), P(axis, None))
        out_specs = P(axis, None)

    @functools.partial(shard_map, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    def inner(xl, wl):
        return gemm_collective(xl, wl, axis_name=axis, axis_size=s_,
                               variant=variant, mode=mode,
                               out_dtype=out_dtype, shard=shard, plan=plan)

    return inner(x, w)
