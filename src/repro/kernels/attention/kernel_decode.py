"""Split-KV flash-decode Pallas kernel (q_len = 1), contiguous and paged.

Autoregressive decode is the paper's memory-bound regime (Fig. 9, Tab. 1's
GQA rows): per generated token every KV byte is read exactly once, so the
kernel's only job is to stream the cache at full HBM bandwidth. The split-KV
shape does that with a grid over (batch, kv_head, kv_split): each grid cell
streams one KV split, computes a partial softmax-attention over it with the
whole GQA group packed into the q tile rows (q is (group, head_dim) — MHA is
group == 1), and writes an unnormalized partial output plus its online-
softmax (m, l) statistics. A cheap jnp log-sum-exp combine merges the splits
exactly. Splitting the KV axis manufactures grid parallelism when
batch * kv_heads alone is too small to keep the DMA pipeline saturated —
the same reason GPU implementations split KV across SMs.

Two cache layouts share the kernel body:

* :func:`flash_decode` — contiguous (B, Hkv, S, D) caches, ring-buffer
  aware: per-sequence ``lengths`` (scalar-prefetched) give each slot its
  absolute position (slot = pos % S), which drives the validity and
  sliding-window masks.
* :func:`flash_decode_paged` — a (P, Hkv, page, D) page pool indexed
  through a scalar-prefetched per-sequence page table. The pool stays in
  HBM; grid (B, head groups, blocks) walks the table a block of
  ``block_kv // page_size`` whole pages at a time, each page one DMA of
  all the step's heads into a double-buffered VMEM block (all KV heads
  for single-token decode, one head for a tall multi-token tile). Blocks
  at or past a slot's length copy and compute nothing, so a launch costs
  the pages the slots hold, not the bucket's; the next block with work is
  copied while the current one is reduced. Never-written table entries
  point at the reserved null page 0 and are neither copied nor counted.

Policies come from ``repro.core.policy`` (op kind ``attention_decode``,
bandwidth-dominated perf model); block_n is the split size (for the paged
pool, ``resolve_decode_policy`` derives it from the launch shape).

Epilogue chains (DESIGN.md §12) split across the two halves: the gemma2
``softcap`` is per-logit, so it runs inside the split kernels (on the
scaled logits, before masking); the attention ``sink`` is per-*row*, so it
lives in :func:`combine_splits` — the one place decode sees the global
softmax state — where it re-anchors the cross-split max exactly like the
flash store epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import tiles
from repro.core.policy import KernelPolicy

from .epilogue import cap_logits

MASK_VALUE = -1e30


def _split_partials(q, k, v, valid, scale, softcap: float = 0.0):
    """Partial attention of one KV split.

    q: (G, D) f32, k/v: (bkv, D), valid: (bkv,) bool — or (G, bkv) bool
    when rows carry different positions (multi-token verify queries).
    ``softcap``: tanh logit cap applied in-split (0 = off). Returns
    unnormalized (o (G, D) f32, m (G, 1), l (G, 1)) — the stats as
    columns, the layout the kernels store; a fully-masked split yields
    (0, MASK_VALUE, 0) which the combine weights to zero.
    """
    s = jax.lax.dot_general(q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = cap_logits(s, softcap)
    vmask = valid if valid.ndim == 2 else valid[None, :]
    s = jnp.where(vmask, s, MASK_VALUE)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(vmask, p, 0.0)
    l = jnp.sum(p, axis=1, keepdims=True)
    o = jax.lax.dot_general(p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return o, m, l


def combine_splits(o, m, l, sinks=None):
    """Log-sum-exp merge of per-split partials (the split-KV epilogue).

    o: (..., NS, G, D) f32 unnormalized partials; m, l: (..., NS, G).
    Exact: rescales every split to the global running max before summing,
    so the result is independent of the split count. Rows whose every split
    was fully masked (empty sequences) return zeros.

    ``sinks``: optional per-head sink logits, broadcastable against the
    (..., 1, G) cross-split max (flash_decode passes (Hkv, 1, G)). This is
    where decode's sink stage must live — the per-split kernels never see
    the global max, and the sink joins the denominator exactly once: the
    cross-split max is re-anchored at max(m_max, sink) *before* the
    rescale so exp never overflows, then exp(sink - m_tot) joins den. With
    a sink, an empty row's mass all lands on the sink (den == 1, out == 0)
    with no epsilon guard needed.
    """
    m_max = jnp.max(m, axis=-2, keepdims=True)
    if sinks is not None:
        m_tot = jnp.maximum(m_max, sinks)            # (..., 1, G)
        alpha = jnp.exp(m - m_tot)
        den = jnp.sum(l * alpha, axis=-2) + jnp.exp(sinks - m_tot)[..., 0, :]
        num = jnp.sum(o * alpha[..., None], axis=-3)
        return num / den[..., None]
    alpha = jnp.exp(m - m_max)                       # (..., NS, G)
    den = jnp.sum(l * alpha, axis=-2)                # (..., G)
    num = jnp.sum(o * alpha[..., None], axis=-3)     # (..., G, D)
    out = num / jnp.maximum(den, 1e-30)[..., None]
    return jnp.where((den > 0.0)[..., None], out, 0.0)


def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                   block_kv: int, slots: int, scale: float,
                   window: int | None, softcap: float = 0.0):
    """Contiguous/ring variant: grid (B, Hkv, n_splits)."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = lengths_ref[b]
    pos = length - 1                                 # last written position
    cur = jax.lax.rem(jax.lax.rem(pos, slots) + slots, slots)
    idx = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_kv,), 0)
    # ring-aware absolute position of each slot (dense caches degenerate to
    # actual == idx); empty rows (length == 0) mask everything.
    actual = jnp.where(idx <= cur, pos - cur + idx, pos - cur - slots + idx)
    valid = (actual >= 0) & (actual <= pos)
    if window is not None:
        valid &= (pos - actual) < window
    o, m, l = _split_partials(q_ref[0, 0].astype(jnp.float32),
                              k_ref[0, 0], v_ref[0, 0], valid, scale, softcap)
    o_ref[0, 0, 0] = o
    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l


def _decode_kernel_paged(page_table_ref, lengths_ref, q_ref, k_hbm, v_hbm,
                         o_ref, m_ref, l_ref, k_buf, v_buf, k_sem, v_sem,
                         state, *, pages_per_block: int, page_size: int,
                         scale: float, window: int | None,
                         softcap: float = 0.0, q_tokens: int = 1):
    """Paged variant: grid (B, head groups, blocks); a step walks one block
    of ``pages_per_block`` pages for every head of its group.

    K/V stay in HBM. Each page of a block is one DMA of
    ``(heads, page, D)`` into a two-slot VMEM buffer; a step starts the
    copies of the next grid step that has work before it computes, so
    the pool streams while the previous block is reduced. A block whose
    first token lies at or past the slot's length copies and computes
    nothing and writes the empty partial (0, MASK_VALUE, 0). Inside a
    block only the pages the slot has written are copied; the buffer is
    zeroed once, so a page slot never copied holds finite values, which
    the per-token mask zeroes.

    ``state`` (SMEM): [0] the buffer slot of the next block with work,
    [1] whether its copies have been started.

    ``q_tokens`` > 1 is the speculative-verify / chunk shape: the q tile
    packs T = q_tokens query positions per GQA group row-major
    (row = g*T + t), token t sitting at absolute position
    ``length - T + t``, so each row gets its own causal (and window) mask.
    """
    b, hg, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_b, n_hg, n_blocks = (pl.num_programs(0), pl.num_programs(1),
                           pl.num_programs(2))
    heads, mp = k_buf.shape[1], page_table_ref.shape[1]
    bt = pages_per_block * page_size

    def has_work(bb, jj):
        return jj * bt < lengths_ref[bb]

    def copy_pages(bb, hh, jj, slot, start):
        """Start (or wait for) the K and V copies of block jj of slot bb's
        head group hh into buffer ``slot``: one copy per page holding
        written tokens."""
        n_live = jnp.minimum(pl.cdiv(lengths_ref[bb] - jj * bt, page_size),
                             pages_per_block)
        src = pl.ds(hh * heads, heads)

        def page(i, carry):
            # a table shorter than the block: its last entry, never live
            pid = page_table_ref[bb, jnp.minimum(jj * pages_per_block + i,
                                                 mp - 1)]
            dst = pl.ds(i * page_size, page_size)
            for hbm, buf, sem in ((k_hbm, k_buf, k_sem),
                                  (v_hbm, v_buf, v_sem)):
                copy = pltpu.make_async_copy(hbm.at[pid, src],
                                             buf.at[slot, :, dst],
                                             sem.at[slot])
                if start:
                    copy.start()
                else:
                    copy.wait()
            return carry

        jax.lax.fori_loop(0, n_live, page, 0)

    def next_step(bb, hh, jj):
        """The first grid step after (bb, hh, jj) that has work; its slot
        index is n_b when none has."""
        more_blocks = (jj + 1 < n_blocks) & has_work(bb, jj + 1)
        same_slot = more_blocks | (hh + 1 < n_hg)
        later = jax.lax.fori_loop(
            jnp.where(same_slot, n_b, bb + 1), n_b,
            lambda i, f: jnp.where((f == n_b) & (lengths_ref[i] > 0), i, f),
            n_b)
        return (jnp.where(same_slot, bb, later),
                jnp.where(more_blocks, hh, jnp.where(same_slot, hh + 1, 0)),
                jnp.where(more_blocks, jj + 1, 0))

    @pl.when((b == 0) & (hg == 0) & (j == 0))
    def _init():
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        state[0] = 0
        state[1] = 0

    @pl.when(has_work(b, j))
    def _compute():
        slot = state[0]

        @pl.when(state[1] == 0)
        def _():                          # the first block with work
            copy_pages(b, hg, j, slot, start=True)

        nb, nh, nj = next_step(b, hg, j)

        @pl.when(nb < n_b)
        def _():
            copy_pages(nb, nh, nj, 1 - slot, start=True)

        state[0] = 1 - slot
        state[1] = (nb < n_b).astype(jnp.int32)
        copy_pages(b, hg, j, slot, start=False)

        length = lengths_ref[b]
        rows = q_ref.shape[2]
        if q_tokens == 1:
            idx = j * bt + jax.lax.broadcasted_iota(jnp.int32, (bt,), 0)
            valid = idx < length
            if window is not None:
                valid &= (length - 1 - idx) < window
        else:
            pos_row = length - q_tokens + (
                jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 0)
                % q_tokens)
            idx = j * bt + jax.lax.broadcasted_iota(jnp.int32, (rows, bt), 1)
            valid = idx <= pos_row
            if window is not None:
                valid &= (pos_row - idx) < window
        for h in range(heads):
            o, m, l = _split_partials(q_ref[0, h].astype(jnp.float32),
                                      k_buf[slot, h], v_buf[slot, h], valid,
                                      scale, softcap)
            o_ref[0, h, 0] = o
            m_ref[0, h, 0] = m
            l_ref[0, h, 0] = l

    @pl.when(jnp.logical_not(has_work(b, j)))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[...] = jnp.zeros_like(l_ref)


def _partial_specs(b, hkv, n_splits, g, d, heads=1):
    """(out_specs, out_shapes) of the per-split partials + stats, ``heads``
    KV heads a grid step. The stats are (g, 1) columns: every block's
    trailing two dims equal the array's, which the TPU lowering requires
    for blocks this small."""
    part_map = lambda b_, h_, j_, *_: (b_, h_, j_, 0, 0)
    out_specs = [
        tiles.block_spec((1, heads, 1, g, d), part_map, jnp.float32,
                         allow_ragged_minor=True),   # q rows = GQA group
        pl.BlockSpec((1, heads, 1, g, 1), part_map),
        pl.BlockSpec((1, heads, 1, g, 1), part_map),
    ]
    out_shapes = [
        jax.ShapeDtypeStruct((b, hkv, n_splits, g, d), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, n_splits, g, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, hkv, n_splits, g, 1), jnp.float32),
    ]
    return out_specs, out_shapes


@functools.partial(
    jax.jit,
    static_argnames=("policy", "window", "logit_scale", "softcap",
                     "interpret"),
)
def flash_decode(q, k, v, lengths, *, policy: KernelPolicy,
                 window: int | None = None,
                 logit_scale: float | None = None,
                 softcap: float = 0.0, sinks=None,
                 interpret: bool = True):
    """Split-KV decode over a contiguous (possibly ring) KV cache.

    q: (B, Hkv, G, D) group-packed queries; k/v: (B, Hkv, S, D);
    lengths: (B,) int32 tokens written so far (ring semantics when
    lengths > S). ``softcap``: in-kernel tanh logit cap; ``sinks``: (H,)
    per-query-head sink logits, folded in by the LSE combine. Returns
    (B, Hkv, G, D) in q.dtype.
    """
    b, hkv, g, d = q.shape
    slots = k.shape[2]
    block_kv = min(policy.block_kv, slots)
    assert slots % block_kv == 0, (slots, block_kv)
    n_splits = slots // block_kv
    scale = logit_scale if logit_scale is not None else d ** -0.5
    policy.check()
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)

    ragged_kv = tiles.shape_ragged(slots, d, k.dtype)
    q_map = lambda b_, h_, j_, *_: (b_, h_, 0, 0)
    kv_map = lambda b_, h_, j_, *_: (b_, h_, j_, 0)
    out_specs, out_shapes = _partial_specs(b, hkv, n_splits, g, d)

    kernel = functools.partial(_decode_kernel, block_kv=block_kv, slots=slots,
                               scale=scale, window=window, softcap=softcap)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, n_splits),
            in_specs=[
                tiles.block_spec((1, 1, g, d), q_map, q.dtype,
                                 allow_ragged_minor=True),  # tiny q tile
                tiles.block_spec((1, 1, block_kv, d), kv_map, k.dtype,
                                 allow_ragged_minor=ragged_kv),
                tiles.block_spec((1, 1, block_kv, d), kv_map, v.dtype,
                                 allow_ragged_minor=ragged_kv),
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shapes,
        compiler_params=tiles.compiler_params(),
        interpret=interpret,
    )(lengths, q, k, v)
    if sinks is not None:
        sinks = jnp.asarray(sinks, jnp.float32).reshape(hkv, 1, g)
    return combine_splits(o, m[..., 0], l[..., 0],
                          sinks=sinks).astype(q.dtype)


def paged_heads_per_step(kv_heads: int, q_tokens: int) -> int:
    """KV heads one paged grid step computes: all of them for single-token
    decode (a page's heads are one contiguous DMA), one for the tall
    multi-token tile of a chunk or verify step."""
    return kv_heads if q_tokens == 1 else 1


def paged_vmem_bytes(*, kv_heads: int, group: int, q_tokens: int,
                     pages_per_block: int, page_size: int, head_dim: int,
                     dtype) -> int:
    """VMEM working set of one :func:`flash_decode_paged` step: the K/V
    block buffers (two slots each), the pipelined q, partial and stat
    blocks (two buffers each), and one head's f32 logits, probabilities
    and mask over the block."""
    heads = paged_heads_per_step(kv_heads, q_tokens)
    rows, bt = group * q_tokens, pages_per_block * page_size
    kv = 2 * 2 * tiles.padded_tile_bytes((heads, bt, head_dim), dtype)
    io = 2 * (tiles.padded_tile_bytes((heads, rows, head_dim), dtype)
              + tiles.padded_tile_bytes((heads, rows, head_dim), jnp.float32)
              + 2 * tiles.padded_tile_bytes((heads, rows, 1), jnp.float32))
    return kv + io + 3 * tiles.padded_tile_bytes((rows, bt), jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("policy", "window", "logit_scale", "softcap",
                     "interpret", "q_tokens"),
)
def flash_decode_paged(q, k_pages, v_pages, page_table, lengths, *,
                       policy: KernelPolicy, window: int | None = None,
                       logit_scale: float | None = None,
                       softcap: float = 0.0, sinks=None,
                       interpret: bool = True, q_tokens: int = 1):
    """Split-KV decode over a paged KV pool (one split == one block of
    ``policy.block_kv // page_size`` pages).

    q: (B, Hkv, G, D); k_pages/v_pages: (P, Hkv, page_size, D) physical
    pools; page_table: (B, MP) int32 physical page ids (0 = reserved null
    page for never-written entries); lengths: (B,) tokens written so far.
    ``softcap``/``sinks`` as in :func:`flash_decode`. Returns
    (B, Hkv, G, D) in q.dtype.

    ``q_tokens`` > 1: G packs group * q_tokens rows (row = g*T + t) and
    row t attends through position ``lengths - q_tokens + t`` — the
    speculative-decoding verify step and the prefill chunk, which stream
    the KV pool exactly once for all T tokens.
    """
    b, hkv, g, d = q.shape
    n_pages, _, page_size, _ = k_pages.shape
    mp = page_table.shape[1]
    assert policy.block_kv % page_size == 0, (policy.block_kv, page_size)
    ppb = policy.block_kv // page_size
    n_blocks = pl.cdiv(mp, ppb)
    heads = paged_heads_per_step(hkv, q_tokens)
    scale = logit_scale if logit_scale is not None else d ** -0.5
    policy.check()
    used = paged_vmem_bytes(kv_heads=hkv, group=g // q_tokens,
                            q_tokens=q_tokens, pages_per_block=ppb,
                            page_size=page_size, head_dim=d,
                            dtype=k_pages.dtype)
    assert used <= tiles.VMEM_BYTES, (used, ppb)
    page_table = jnp.asarray(page_table, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32).reshape(b)

    q_map = lambda b_, h_, j_, *_: (b_, h_, 0, 0)
    out_specs, out_shapes = _partial_specs(b, hkv, n_blocks, g, d, heads)
    kernel = functools.partial(_decode_kernel_paged, pages_per_block=ppb,
                               page_size=page_size, scale=scale,
                               window=window, softcap=softcap,
                               q_tokens=q_tokens)
    buf = pltpu.VMEM((2, heads, ppb * page_size, d), k_pages.dtype)
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv // heads, n_blocks),
            in_specs=[
                tiles.block_spec((1, heads, g, d), q_map, q.dtype,
                                 allow_ragged_minor=True),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=out_specs,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((2,), jnp.int32)],
        ),
        out_shape=out_shapes,
        # a step starts the copies of the next step with work: the grid
        # runs in order
        compiler_params=tiles.compiler_params(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(page_table, lengths, q, k_pages, v_pages)
    if sinks is not None:
        sinks = jnp.asarray(sinks, jnp.float32).reshape(hkv, 1, g)
    return combine_splits(o, m[..., 0], l[..., 0],
                          sinks=sinks).astype(q.dtype)
