"""Analytic policy autotuner (paper §3.3-3.4; Tab. 2-4 models as the cost fn).

Lurati et al. ("Bringing Auto-tuning to HIP", 2024) show that most of the
AMD-vs-baseline gap lives in tuning-parameter search; HipKittens' answer is a
small, structured search space (schedule × tile × traversal). This module is
that search, run against the repo's *analytic* models instead of hardware:

  1. :func:`candidate_policies` enumerates every VMEM-legal
     :class:`~repro.core.policy.KernelPolicy` whose blocks tile the problem
     shape (divisibility + native alignment via the Schedule blocks);
  2. :func:`score_policy` ranks a candidate with the existing models —
     ``perf_model.gemm_step_model`` / ``attention_step_model`` for pipeline
     time, ``grid_swizzle.dma_bytes`` for the Pallas-revisit HBM traffic of
     its traversal order (and optionally ``cache_model.simulate_gemm_schedule``
     for the multi-executor hierarchy, see :func:`refine_with_cache_model`);
  3. :func:`select_policy` memoizes the winner in an in-process cache keyed by
     (kernel kind, shape-bucket, dtype) so model tracing re-resolves for free.

Deterministic by construction: candidates are scored with pure functions and
ties break on (modeled time, modeled DMA bytes, policy key).

See DESIGN.md §5 for where this sits in the policy resolution order.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable, Optional

from repro import obs

from . import perf_model as pm
from . import tiles
from .grid_swizzle import ROW_MAJOR, SwizzleConfig, dma_bytes
from .policy import KernelPolicy, OP_KINDS, make_policy, policy_from_spec
from .schedule import Schedule

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1,
                "float8_e4m3fn": 1, "float8_e5m2": 1}

# The per-grid-step fixed cost and the vector-unit throughput both live on
# ChipSpec now (calibratable, DESIGN.md §15): chip.step_overhead_s models the
# pipeline bubble / bookkeeping of a Pallas grid step (only its *relative*
# effect matters: it breaks ties toward fewer, larger blocks for memory-bound
# 1-D ops); chip.vector_throughput() prices softmax/norm vector work.


@dataclasses.dataclass(frozen=True)
class OpSignature:
    """What the autotuner needs to know about one kernel launch.

    ``shape`` per op kind:
      gemm             (m, n, k)
      attention_fwd    (batch, heads, seq_q, seq_kv, head_dim)
      attention_bwd    (batch, heads, seq_q, seq_kv, head_dim)
      attention_decode (batch, kv_heads, group, kv_len, head_dim)
      fused_norm       (rows, d)
      rope             (batch, heads, seq, head_dim)

    ``epilogue`` is the fused store chain the launch will run, carried
    opaquely. For gemm/gemm_bwd it is a
    :class:`repro.kernels.gemm.epilogue.Epilogue`: its extra operands
    change both the legal candidate set (VMEM, whole-head block_n for
    rope) and the scored traffic. For the attention ops it is an
    :class:`repro.kernels.attention.epilogue.AttnEpilogue` (softcap /
    attention-sink stages inside the online-softmax loop and store):
    stateless on the candidate set beyond the tiny sink-operand VMEM
    charge, but its streamed sink row adds to the scored traffic and it
    rides the returned policy into the kernels.
    ``prologue`` (gemm/gemm_bwd only) is the fused A-operand chain
    (:class:`repro.kernels.gemm.prologue.Prologue`)
    — a recompute-path norm prologue pins block_k to the full feature dim
    and charges the per-A-tile norm recompute to the compute term.

    ``variant`` (gemm_bwd only) names which bwd launch of the fused
    backward (DESIGN.md §11) this is: ``'da'`` (shape (M, K, N) — out dA,
    contraction over N) or ``'db'`` (shape (K, N, M) — out dB[, dB2],
    contraction over M). The chains pin different dims per variant: a norm
    prologue pins dA's out-column block to full K (its row reductions need
    whole feature rows) and — on the recompute stats path — dB's out-row
    block to full K (the streamed A tile spans whole rows, the fwd rule);
    a rope epilogue pins the dim its g tiles rotate along to whole heads
    (dA: the contraction block; dB: the out-column block).

    ``shard`` (DESIGN.md §16) is the launch's
    :class:`repro.distributed.sharding.ShardSpec` — mesh axes × operand
    partition × collective — carried opaquely like the chains. It joins
    the bucket so a sharded launch never shares a memo cell with its
    single-device twin (the candidate set is the same — per-rank local
    shapes are what's scored — but the plan audit and pretuned tables key
    on it).
    """

    op: str
    shape: tuple
    dtype: str = "bfloat16"
    causal: bool = False
    epilogue: Optional[object] = None
    prologue: Optional[object] = None
    variant: str = ""
    shard: Optional[object] = None

    def __post_init__(self):
        if self.op not in OP_KINDS:
            raise ValueError(f"unknown op kind {self.op!r}")
        if self.op == "gemm_bwd" and self.variant not in ("da", "db"):
            raise ValueError(f"gemm_bwd needs variant 'da' or 'db', "
                             f"got {self.variant!r}")
        if self.variant and self.op != "gemm_bwd":
            raise ValueError("variant is only meaningful for gemm_bwd")

    def bucket(self) -> tuple:
        """Policy-cache key. Tile-constrained dims stay exact (a block must
        divide them); pure batch-like dims round up to the next power of two
        so e.g. batch 48 and 64 share one compiled bucket."""
        def pow2(x: int) -> int:
            return 1 << max(0, (x - 1).bit_length())

        if self.op in ("attention_fwd", "attention_bwd"):
            b, h, sq, skv, d = self.shape
            shape = (pow2(b), pow2(h), sq, skv, d)
        elif self.op == "attention_decode":
            # kv_len stays exact (the split size must divide it); batch and
            # kv_heads are batch-like; group is tiny and kept exact (it is
            # the q-tile row count).
            b, hkv, g, skv, d = self.shape
            shape = (pow2(b), pow2(hkv), g, skv, d)
        elif self.op == "rope":
            b, h, s, d = self.shape
            shape = (pow2(b), pow2(h), s, d)
        else:
            shape = tuple(self.shape)
        return (self.op, shape, self.dtype, self.causal, self.epilogue,
                self.prologue, self.variant, self.shard)


@dataclasses.dataclass(frozen=True)
class PolicyScore:
    time_s: float        # modeled wall time of the whole op (lower is better)
    dma_bytes: int       # modeled HBM→VMEM traffic under the traversal order
    detail: tuple = ()   # (key, value) pairs for reports

    def rank_key(self, policy: KernelPolicy) -> tuple:
        return (self.time_s, self.dma_bytes, repr(policy.cache_key()))


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

def _block_candidates(dim: int, align: int, cap: int) -> list:
    """Aligned divisors of ``dim`` up to ``cap``; always non-empty.

    When no aligned divisor exists (dim itself unaligned), falls back to the
    whole dim / largest divisor — the kernels accept those because a block
    covering an unaligned problem dim pads exactly once (the same padding
    the pre-policy raw BlockSpecs produced); see tiles.block_spec callers.
    """
    cands = [b for b in range(align, min(dim, cap) + 1, align) if dim % b == 0]
    if dim <= cap and dim not in cands:
        cands.append(dim)  # the whole dim always tiles itself
    if not cands:
        cands = [max(b for b in range(1, cap + 1) if dim % b == 0)]
    return sorted(set(cands))


def _sublane(dtype: str) -> int:
    return tiles.native_tiling(
        dtype if dtype in _DTYPE_BYTES else "bfloat16")[0]


def _swizzle_candidates(num_rows: int, num_cols: int) -> list:
    """Traversal orders worth scoring for a 2-D block grid: row-major plus
    Algorithm-1 windows (chiplet step off — single-core Pallas use)."""
    cands = [ROW_MAJOR]
    seen = set()
    for w in (2, 4, 8, num_rows):
        if 1 < w <= num_rows and w not in seen:
            seen.add(w)
            cands.append(SwizzleConfig(window=w, enable_chiplet=False))
    return cands


def _head_multiple_candidates(dim: int, hd: int, base: list) -> list:
    """Restrict block candidates to head_dim multiples (rope's whole-head
    rule), unioning head_dim-aligned divisors for non-128-aligned heads.
    Lane-aligned multiples are preferred when any exist (a 64-wide tile on
    an aligned problem dim would trip tiles.block_spec's strict gate)."""
    cands = sorted(b for b in
                   set(base) | set(_block_candidates(dim, hd, 512))
                   if b % hd == 0)
    aligned = [b for b in cands if b % tiles.LANE == 0]
    return aligned or cands


def candidate_policies(sig: OpSignature,
                       swizzle: Optional[SwizzleConfig] = None) -> list:
    """Every legal candidate for ``sig``: blocks tile the shape AND the
    pipelined working set fits VMEM (Tab. 2's feasibility rule).

    ``swizzle`` restricts the traversal-order axis of the search to one
    requested SwizzleConfig (the legacy ``gemm(swizzle=...)`` shim and the
    bwd launches, which pin the fwd policy's traversal, use this) — block
    and pipeline-depth candidates are still fully enumerated.
    """
    dtype = "bfloat16" if sig.dtype not in _DTYPE_BYTES else sig.dtype
    out = []

    def swizzles(rows, cols):
        return [swizzle] if swizzle is not None else \
            _swizzle_candidates(rows, cols)

    if sig.op in ("gemm", "gemm_bwd"):
        m, n, k = sig.shape
        ep = sig.epilogue
        pro = sig.prologue
        bm_cands = _block_candidates(m, 128, 512)
        bn_cands = _block_candidates(n, 128, 512)
        bk_cands = _block_candidates(k, 128, 512)
        has_rope = ep is not None and getattr(ep, "rope", False)
        has_pro = pro is not None and not getattr(pro, "is_identity", True)
        if sig.op == "gemm":
            if has_rope:
                # rope rotates whole heads per tile: block_n must be a
                # head_dim multiple (head_dim-aligned divisors cover
                # non-128-aligned heads)
                bn_cands = _head_multiple_candidates(n, ep.head_dim, bn_cands)
            if pro is not None and getattr(pro, "needs_full_k", False):
                # recompute-path norm prologue: row stats come from the A
                # tile itself, so the tile must span the full feature dim
                bk_cands = [k]
        elif sig.variant == "da":
            if has_rope:  # g tiles rotate along the contraction (N) dim
                bk_cands = _head_multiple_candidates(k, ep.head_dim, bk_cands)
            if has_pro:   # norm-transpose row reductions span full K
                bn_cands = [n]
        else:  # 'db'
            if has_rope:  # g tiles rotate along the output-column (N) dim
                bn_cands = _head_multiple_candidates(n, ep.head_dim, bn_cands)
            if pro is not None and getattr(pro, "needs_full_k", False):
                bm_cands = [m]  # streamed A tiles span whole feature rows
        for bm in bm_cands:
            for bn in bn_cands:
                for bk in bk_cands:
                    for nbuf in (2, 3):
                        sched = Schedule(f"auto_g{nbuf}", nbuf, bm, bn, bk)
                        rows, cols = m // bm, n // bn
                        for sw in swizzles(rows, cols):
                            pol = KernelPolicy(sig.op, sched, sw,
                                               in_dtype=dtype, epilogue=ep,
                                               prologue=pro)
                            if pol.is_legal():
                                out.append(pol)

    elif sig.op in ("attention_fwd", "attention_bwd"):
        b, h, sq, skv, d = sig.shape
        for bq in _block_candidates(sq, 128, 512):
            for bkv in _block_candidates(skv, 128, 512):
                sched = Schedule("auto_a", 2, bq, bkv, d)
                pol = KernelPolicy(sig.op, sched, ROW_MAJOR, in_dtype=dtype,
                                   epilogue=sig.epilogue)
                if pol.is_legal():
                    out.append(pol)

    elif sig.op == "attention_decode":
        b, hkv, g, skv, d = sig.shape
        # block_n is the KV-split size: one split per grid step. The q tile
        # holds the packed GQA group (block_m = group; tiny, Pallas pads it).
        for bkv in _block_candidates(skv, _sublane(dtype), 2048):
            pol = make_policy("attention_decode", block_m=g, block_n=bkv,
                              block_k=d, in_dtype=dtype, name="auto_d",
                              epilogue=sig.epilogue)
            if pol.is_legal():
                out.append(pol)

    elif sig.op == "fused_norm":
        rows, d = sig.shape
        for br in _block_candidates(rows, _sublane(dtype), 1024):
            pol = make_policy("fused_norm", block_m=br, block_k=d,
                              in_dtype=dtype, name="auto_n")
            if pol.is_legal():
                out.append(pol)

    elif sig.op == "rope":
        b, h, s, d = sig.shape
        for bs in _block_candidates(s, _sublane(dtype), 1024):
            pol = make_policy("rope", block_m=bs, block_k=d,
                              in_dtype=dtype, name="auto_r")
            if pol.is_legal():
                out.append(pol)

    return out


# ---------------------------------------------------------------------------
# Scoring
# ---------------------------------------------------------------------------

def gemm_traffic_bytes(policy: KernelPolicy, m: int, n: int, k: int,
                       dtype_bytes: int) -> int:
    """Modeled HBM→VMEM bytes of the full GEMM under the policy's traversal
    (full-K panels, Pallas consecutive-revisit rule — grid_swizzle.dma_bytes).

    An attached epilogue adds its streamed operands: the gate's B2 panel
    follows B's revisit pattern exactly (doubled B traffic), the rest
    (bias/residual/tables) stream once with the output tiles. An attached
    prologue adds its gamma/beta rows and fast-path stats columns — the
    *eliminated* normed-activation round trip is chain-model territory
    (perf_model), not this per-launch count.
    """
    rows, cols = m // policy.block_m, n // policy.block_n
    a_panel = policy.block_m * k * dtype_bytes
    b_panel = k * policy.block_n * dtype_bytes
    ep = policy.epilogue
    if ep is not None and getattr(ep, "gate", False):
        b_panel *= 2
    traffic = dma_bytes(policy.swizzle, rows, cols, a_panel, b_panel)
    if ep is not None:
        traffic += ep.extra_read_bytes(m, n, dtype_bytes)
    pro = policy.prologue
    if pro is not None:
        traffic += pro.extra_read_bytes(m, k, dtype_bytes)
    return traffic


def gemm_bwd_traffic_bytes(policy: KernelPolicy, m: int, n: int, k: int,
                           dtype_bytes: int, variant: str) -> int:
    """Modeled HBM→VMEM bytes of one fused-backward launch (DESIGN.md §11).

    The launch is a GEMM of its own (m, n, k) shape under the policy's
    traversal, with the chain's extra streams on top: the saved
    preactivations ride the cotangent panel (the g-side operand — the A
    side for dA, the B side for dB) in the MXU input dtype; the dual-GEMM
    gate doubles the *weight* panel for dA (B and B2 both stream) and costs
    dB nothing extra on reads (dB2 shares the same A and g streams); a norm
    prologue adds the raw-A reads for the tile-wise norm transpose (dA: one
    (M, K) pass with the output tiles; dB: the A panel IS the primal
    operand) plus the gamma/beta/stats rows.
    """
    rows, cols = m // policy.block_m, n // policy.block_n
    a_panel = policy.block_m * k * dtype_bytes
    b_panel = k * policy.block_n * dtype_bytes
    ep = policy.epilogue
    pro = policy.prologue
    n_saved = getattr(ep, "saved_accumulators", 0) if ep is not None else 0
    # scale chains save fp32 preacts (Epilogue.preact_keeps_f32)
    p_bytes = 4 if (ep is not None and getattr(ep, "preact_keeps_f32",
                                               False)) else dtype_bytes
    extra = 0
    if variant == "da":
        a_panel += policy.block_m * k * p_bytes * n_saved      # preacts
        if ep is not None and getattr(ep, "gate", False):
            b_panel *= 2                                       # B and B2
        if pro is not None and not getattr(pro, "is_identity", True):
            extra += m * n * dtype_bytes   # raw A, once per output tile
            extra += pro.extra_read_bytes(m, n, dtype_bytes)
    else:  # 'db'
        b_panel += k * policy.block_n * p_bytes * n_saved      # preacts
        if pro is not None and not getattr(pro, "is_identity", True):
            extra += pro.extra_read_bytes(k, m, dtype_bytes)
    traffic = dma_bytes(policy.swizzle, rows, cols, a_panel, b_panel) + extra
    if ep is not None:
        # bias/scale/table streams are read by the transpose like the fwd
        # store read them — over the *forward* (M, N) dims, which the
        # launch shape encodes per variant: da is (M, K, N), db is
        # (K, N, M). (dresidual is the identity — no stream.)
        fwd_m, fwd_n = (m, k) if variant == "da" else (k, n)
        streams = ep.extra_read_bytes(fwd_m, fwd_n, dtype_bytes)
        if getattr(ep, "residual", False):
            streams -= fwd_m * fwd_n * dtype_bytes
        traffic += streams
    return traffic


def score_policy(sig: OpSignature, policy: KernelPolicy,
                 chip: pm.ChipSpec = pm.V5E) -> PolicyScore:
    dtype_bytes = _DTYPE_BYTES.get(sig.dtype, 2)

    if sig.op in ("gemm", "gemm_bwd"):
        m, n, k = sig.shape
        step = pm.gemm_step_model(policy.schedule, k_total=k,
                                  dtype_bytes=dtype_bytes, chip=chip)
        if not step["feasible"]:
            return PolicyScore(math.inf, 2**62)
        n_blocks = (m // policy.block_m) * (n // policy.block_n)
        tflops = step["modeled_tflops"]
        n_acc = 2 if (policy.epilogue is not None
                      and getattr(policy.epilogue, "gate", False)) else 1
        compute_s = (n_acc * 2.0 * m * n * k / (tflops * 1e12)
                     if tflops else math.inf)
        pro = policy.prologue
        if pro is not None and not getattr(pro, "is_identity", True):
            # per-A-tile norm work: each A panel is re-processed once per
            # output-column block it is revisited for — vector-unit work
            # bought against the eliminated HBM round trip. The recompute
            # path re-derives row stats (~8 ops/element); the
            # precomputed-stats fast path only applies the affine transform
            # (~3 ops/element, stats streamed). The bwd launches pay the
            # same per-tile rate: dB renorms its A stream once per
            # output-column visit like the fwd; dA runs the norm transpose
            # exactly once per full-K store tile — M*K elements total, no
            # revisit factor (its out-column block is pinned to K).
            ops = 3.0 if getattr(pro, "precomputed_stats", False) else 8.0
            if sig.op == "gemm_bwd" and sig.variant == "da":
                norm_elems = m * n          # the (M, K) store tiles, once
            else:
                norm_elems = (n // policy.block_n) * m * k
            compute_s += norm_elems * ops / chip.vector_throughput()
        if sig.op == "gemm_bwd":
            traffic = gemm_bwd_traffic_bytes(policy, m, n, k, dtype_bytes,
                                             sig.variant)
        else:
            traffic = gemm_traffic_bytes(policy, m, n, k, dtype_bytes)
        memory_s = traffic / chip.hbm_bw
        time_s = max(compute_s, memory_s) + n_blocks * chip.step_overhead_s
        return PolicyScore(time_s, traffic,
                           (("bound", step["bound"]),
                            ("ai", round(step["arithmetic_intensity"], 1))))

    if sig.op in ("attention_fwd", "attention_bwd"):
        b, h, sq, skv, d = sig.shape
        step = pm.attention_step_model(
            block_q=policy.block_q, block_kv=policy.block_kv, head_dim=d,
            seq_len=skv, causal=sig.causal, dtype_bytes=dtype_bytes, chip=chip)
        nq = sq // policy.block_q
        useful = 4.0 * b * h * sq * skv * d * (0.5 if sig.causal else 1.0)
        tflops = step["modeled_tflops"]
        time_s = useful / (tflops * 1e12) if tflops else math.inf
        # K/V are re-streamed once per q block; q/o stream once.
        kv_frac = (0.5 if sig.causal else 1.0)
        traffic = int(b * h * (nq * kv_frac * 2 * skv * d
                               + 2 * sq * d) * dtype_bytes)
        if sig.op == "attention_bwd":
            time_s *= 2.5   # dq + dkv passes re-read everything
            traffic *= 2
        if policy.epilogue is not None:
            traffic += policy.epilogue.extra_read_bytes(h)
        time_s += b * h * nq * (skv // policy.block_kv) * chip.step_overhead_s
        return PolicyScore(time_s, traffic, (("bound", step["bound"]),))

    if sig.op == "attention_decode":
        b, hkv, g, skv, d = sig.shape
        step = pm.decode_step_model(
            batch=b, kv_heads=hkv, group=g, kv_len=skv, head_dim=d,
            block_kv=policy.block_kv, dtype_bytes=dtype_bytes, chip=chip)
        sink_bytes = (policy.epilogue.extra_read_bytes(hkv * g)
                      if policy.epilogue is not None else 0)
        return PolicyScore(step["time_s"],
                           step["kv_bytes"] + step["partial_bytes"]
                           + sink_bytes,
                           (("bound", step["bound"]),
                            ("n_splits", step["n_splits"]),
                            ("utilization", round(step["utilization"], 2))))

    if sig.op == "fused_norm":
        rows, d = sig.shape
        traffic = 4 * rows * d * dtype_bytes
        steps = rows // policy.block_rows
        return PolicyScore(traffic / chip.hbm_bw
                           + steps * chip.step_overhead_s, traffic)

    if sig.op == "rope":
        b, h, s, d = sig.shape
        traffic = b * h * s * d * (2 * dtype_bytes + 8)  # x/out + f32 tables
        steps = b * h * (s // policy.block_rows)
        return PolicyScore(traffic / chip.hbm_bw
                           + steps * chip.step_overhead_s, traffic)

    raise AssertionError(sig.op)


def refine_with_cache_model(sig: OpSignature, policies: Iterable[KernelPolicy],
                            hw=None) -> list:
    """Re-rank GEMM finalists with the two-level cache simulator (Tab. 4).

    Slow (explicit LRU sim) — used by the schedule benchmarks and available
    as ``select_policy(..., cache_sim=True)``; the memoized fast path ranks
    analytically only.
    """
    from .cache_model import CacheHW, simulate_gemm_schedule
    hw = hw if hw is not None else CacheHW.tpu_v5e()
    m, n, k = sig.shape
    scored = []
    for pol in policies:
        r = simulate_gemm_schedule(pol.swizzle, m=m, n=n, k=k,
                                   block_m=pol.block_m, block_n=pol.block_n,
                                   block_k=pol.block_k, hw=hw)
        scored.append((r.modeled_time_s, repr(pol.cache_key()), pol, r))
    scored.sort(key=lambda t: t[:2])
    return [(pol, r) for _, _, pol, r in scored]


# ---------------------------------------------------------------------------
# Pretuned policy tables (DESIGN.md §15): measurement-grounded winners from
# repro.core.calibrate, persisted as versioned JSON and consulted AHEAD of
# the analytic ranking. The table also carries a fitted ChipSpec, which
# becomes the default chip for every subsequent analytic score — so even
# cells the table doesn't pin are ranked with measured coefficients.
# ---------------------------------------------------------------------------

PRETUNED_SCHEMA_VERSION = 1

# Module-global like the memo caches: one pretuned table per process. ``gen``
# is the calibration-table generation counter — it is part of every memo key
# below, so installing/refreshing/clearing a table invalidates all cached
# winners in-process (the PR 9 staleness fix) without flushing audits by hand.
_PRETUNED: dict = {"table": None, "chip": None, "gen": 0}


def pretuned_generation() -> int:
    return _PRETUNED["gen"]


def active_pretuned() -> Optional[dict]:
    """The installed pretuned table, or None."""
    return _PRETUNED["table"]


# The analytic ChipSpec of each TPU ``device_kind`` the model describes.
CHIPS_BY_DEVICE_KIND = {"TPU v5 lite": pm.V5E}


def active_chip() -> pm.ChipSpec:
    """The chip every ``chip=None`` ranking resolves against: the installed
    table's fitted ChipSpec when present; else, on a TPU backend, the spec
    of the attached chip's ``device_kind`` (a kind the model does not
    describe raises — tiles ranked for another chip are no default); else
    the analytic V5E defaults."""
    chip = _PRETUNED["chip"]
    return chip if chip is not None else _backend_chip()


@functools.lru_cache(maxsize=None)
def _backend_chip() -> pm.ChipSpec:
    import jax

    if jax.default_backend() != "tpu":
        return pm.V5E
    kind = jax.devices()[0].device_kind
    if kind not in CHIPS_BY_DEVICE_KIND:
        raise ValueError(
            f"no ChipSpec for TPU device_kind {kind!r}; "
            f"have {sorted(CHIPS_BY_DEVICE_KIND)}")
    return CHIPS_BY_DEVICE_KIND[kind]


def chip_from_dict(d: dict) -> pm.ChipSpec:
    """Rebuild a ChipSpec from a pretuned table's coefficient dict (unknown
    keys ignored — forward-compatible with fitted fields we don't have)."""
    fields = {f.name: f for f in dataclasses.fields(pm.ChipSpec)}
    kw = {}
    for k, v in d.items():
        if k not in fields:
            continue
        if fields[k].type in ("int", int):
            v = int(round(v))
        kw[k] = v
    return dataclasses.replace(pm.V5E, **kw)


def _chain_str(chain) -> str:
    """Stable string form of an epilogue/prologue chain for cell keys.
    Chains expose deterministic ``describe()`` short strings; None is the
    identity."""
    if chain is None:
        return "none"
    d = chain.describe()
    return d if isinstance(d, str) else str(d)


def _shard_str(shard) -> str:
    """Stable string form of a ShardSpec for cell keys / plan audits
    (duck-typed: core never imports repro.distributed)."""
    if shard is None:
        return "none"
    describe = getattr(shard, "describe", None)
    return describe() if callable(describe) else str(shard)


def pretuned_cell_key(sig: OpSignature) -> str:
    """The table key of one policy cell: shape-BUCKET × dtype × chain, as a
    stable string (buckets, not raw shapes, so a table cell covers the same
    launches the in-process memo would)."""
    op, shape, dtype, causal, ep, pro, variant, shard = sig.bucket()
    parts = [op, "x".join(str(x) for x in shape), dtype,
             "causal" if causal else "full",
             f"ep={_chain_str(ep)}", f"pro={_chain_str(pro)}"]
    if variant:
        parts.append(f"var={variant}")
    if shard is not None:
        parts.append(f"shard={_shard_str(shard)}")
    return "|".join(parts)


def pretuned_fusion_key(kind: str, bucket_shape: tuple, dtype: str, *,
                        residual: bool, prenorm: str, backward: bool,
                        causal: bool, softcap: bool, sink: bool,
                        shard=None) -> str:
    """The table key of one fusion-plan cell (mirrors select_fusion's memo).
    Unsharded cells keep the historical key so shipped tables stay valid;
    a ShardSpec appends its stable token."""
    parts = [kind, "x".join(str(x) for x in bucket_shape), dtype,
             f"res={int(residual)}", f"pre={prenorm}",
             f"bwd={int(backward)}", f"causal={int(causal)}",
             f"cap={int(softcap)}", f"sink={int(sink)}"]
    if shard is not None:
        parts.append(f"shard={_shard_str(shard)}")
    return "|".join(parts)


def install_pretuned(table: dict, *, arch: Optional[str] = None) -> bool:
    """Validate and install a pretuned table; True iff installed.

    A schema-version or arch mismatch REJECTS the table (counter-logged,
    previous state untouched) and every selection falls back to the analytic
    ranking — a table fitted on other hardware must never pin winners here.
    ``arch`` overrides the expected platform (defaults to the active JAX
    backend).
    """
    if int(table.get("schema_version", -1)) != PRETUNED_SCHEMA_VERSION:
        obs.incr("autotune.pretuned_rejected_schema")
        return False
    expect = arch
    if expect is None:
        try:
            import jax
            expect = jax.default_backend()
        except Exception:  # pragma: no cover - jax is a hard dep in practice
            expect = None
    if expect is not None and table.get("arch") != expect:
        obs.incr("autotune.pretuned_rejected_arch")
        return False
    chip_d = table.get("chip")
    _PRETUNED.update(table=table,
                     chip=chip_from_dict(chip_d) if chip_d else None)
    _PRETUNED["gen"] += 1
    obs.incr("autotune.pretuned_installed")
    return True


def load_pretuned(path, *, arch: Optional[str] = None) -> bool:
    """Load a pretuned table from a JSON file and install it."""
    import json
    with open(path) as f:
        table = json.load(f)
    return install_pretuned(table, arch=arch)


def use_pretuned(table_or_path, *, arch: Optional[str] = None) -> bool:
    """Install a pretuned table given either a report dict or a JSON path —
    the single entry point serve/train expose as ``pretuned=``."""
    if isinstance(table_or_path, dict):
        return install_pretuned(table_or_path, arch=arch)
    return load_pretuned(table_or_path, arch=arch)


def clear_pretuned() -> None:
    """Drop the installed table (and its fitted chip); bumps the generation
    so memoized pretuned winners can't survive."""
    if _PRETUNED["table"] is not None or _PRETUNED["chip"] is not None:
        _PRETUNED.update(table=None, chip=None)
        _PRETUNED["gen"] += 1


def _sig_fits(sig: OpSignature, pol: KernelPolicy) -> bool:
    """A pinned policy must still tile THIS launch's exact shape and fit
    VMEM — guards hand-edited tables and bucket-rounding edge cases."""
    if sig.op in ("gemm", "gemm_bwd"):
        m, n, k = sig.shape
        ok = pol.fits(m, n, k)
    elif sig.op in ("attention_fwd", "attention_bwd"):
        _, _, sq, skv, d = sig.shape
        ok = pol.fits(sq, skv) and pol.block_k == d
    elif sig.op == "attention_decode":
        _, _, g, skv, d = sig.shape
        ok = pol.block_m == g and skv % pol.block_n == 0 and pol.block_k == d
    elif sig.op == "fused_norm":
        rows, d = sig.shape
        ok = rows % pol.block_rows == 0 and pol.block_k == d
    else:  # rope
        _, _, s, d = sig.shape
        ok = s % pol.block_rows == 0 and pol.block_k == d
    return ok and pol.is_legal()


# ---------------------------------------------------------------------------
# Memoized selection
# ---------------------------------------------------------------------------

_POLICY_CACHE: dict = {}
_CACHE_STATS = {"hits": 0, "misses": 0}
# Audit records live beside the memo caches so a cache *hit* can still
# replay the original decision into the telemetry journal (cached=True) —
# the decision is identical, the rescoring cost is zero (DESIGN.md §13).
_POLICY_AUDIT: dict = {}
_PLAN_AUDIT: dict = {}


def select_policy(op: str, shape, dtype="bfloat16", *, causal: bool = False,
                  epilogue=None, prologue=None, variant: str = "",
                  shard=None,
                  swizzle: Optional[SwizzleConfig] = None,
                  cache_sim: bool = False,
                  chip: Optional[pm.ChipSpec] = None) -> KernelPolicy:
    """The tuned policy for an op signature; memoized per shape-bucket.

    ``epilogue``/``prologue`` (gemm/gemm_bwd only) make the candidate set
    and the traffic model chain-aware; the returned policy carries them.
    ``variant`` ('da'|'db', gemm_bwd only) names the fused-backward launch.
    ``shard`` (a :class:`~repro.distributed.sharding.ShardSpec`, DESIGN.md
    §16) marks the launch as one rank of a sharded op: the shape passed in
    is the per-rank LOCAL shape (which is what the candidate set and the
    traffic model should score), and the spec joins the memo key + audit so
    a sharded launch never aliases its single-device twin's cell.
    ``swizzle`` pins the traversal order while the block/pipeline axes are
    still searched (the legacy ``gemm(swizzle=...)`` shim and the bwd
    launches, which inherit the fwd traversal, resolve through this).

    ``chip=None`` resolves against :func:`active_chip` — the calibrated
    ChipSpec when a pretuned table is installed. An installed table is also
    consulted for a pinned WINNER first (measurement-grounded, DESIGN.md
    §15); analytic ranking is the fallback on any cell miss, and pinning is
    bypassed entirely when the caller constrains the search (``swizzle=`` /
    ``cache_sim=True``) since table winners were measured unconstrained.

    Raises ValueError if no candidate is legal — which a recompute-path
    norm prologue *can* hit (its full-K A tile may not fit VMEM for huge
    feature dims): callers fall back to the standalone-norm plan then.
    """
    if chip is None:
        chip = active_chip()
    sig = OpSignature(op, tuple(int(x) for x in shape), str(dtype),
                      causal=causal, epilogue=epilogue, prologue=prologue,
                      variant=variant, shard=shard)
    key = sig.bucket() + (swizzle, bool(cache_sim), chip.name,
                          _PRETUNED["gen"])
    hit = _POLICY_CACHE.get(key)
    if hit is not None:
        _CACHE_STATS["hits"] += 1
        if obs.enabled():
            audit = _POLICY_AUDIT.get(key)
            if audit is not None:
                obs.plan_decision("policy", op, sig.shape, sig.dtype,
                                  audit["chosen"], audit["candidates"],
                                  cached=True)
        return hit
    _CACHE_STATS["misses"] += 1

    table = _PRETUNED["table"]
    if table is not None and swizzle is None and not cache_sim:
        cell = (table.get("cells") or {}).get(pretuned_cell_key(sig))
        if cell is None:
            obs.incr("autotune.pretuned_cell_miss")
        else:
            pinned = policy_from_spec(cell["policy"], epilogue=epilogue,
                                      prologue=prologue)
            if _sig_fits(sig, pinned):
                obs.incr("autotune.pretuned_hit")
                _POLICY_CACHE[key] = pinned
                audit = {"chosen": dict(pinned.describe(), pretuned=True),
                         "candidates": [
                             {"policy": pinned.schedule.name,
                              "blocks": [pinned.block_m, pinned.block_n,
                                         pinned.block_k],
                              "time_s": cell.get("measured_time_s"),
                              "dma_bytes": None, "chosen": True,
                              "pretuned": True}]}
                _POLICY_AUDIT[key] = audit
                obs.plan_decision("policy", op, sig.shape, sig.dtype,
                                  audit["chosen"], audit["candidates"])
                return pinned
            obs.incr("autotune.pretuned_illegal")

    cands = candidate_policies(sig, swizzle=swizzle)
    if not cands:
        raise ValueError(f"no legal policy for {sig}")
    scored = sorted(cands,
                    key=lambda p: score_policy(sig, p, chip).rank_key(p))
    best = scored[0]
    if cache_sim and sig.op == "gemm":
        finalists = scored[: min(8, len(scored))]
        best = refine_with_cache_model(sig, finalists)[0][0]
    _POLICY_CACHE[key] = best
    # audit: the winner + the top losing candidates with their modeled
    # time/bytes (bounded — a full candidate set can be hundreds deep)
    cand_audit = []
    for p in scored[:8]:
        s = score_policy(sig, p, chip)
        cand_audit.append({"policy": p.schedule.name,
                           "blocks": [p.block_m, p.block_n, p.block_k],
                           "time_s": s.time_s, "dma_bytes": s.dma_bytes,
                           "chosen": p is best})
    audit = {"chosen": best.describe(),
             "candidates": cand_audit}
    _POLICY_AUDIT[key] = audit
    obs.plan_decision("policy", op, sig.shape, sig.dtype,
                      audit["chosen"], audit["candidates"])
    return best


def policy_cache_stats() -> dict:
    return dict(_CACHE_STATS, size=len(_POLICY_CACHE))


def clear_policy_cache() -> None:
    _POLICY_CACHE.clear()
    _PLAN_CACHE.clear()
    _BWD_ROUTE_CACHE.clear()
    _POLICY_AUDIT.clear()
    _PLAN_AUDIT.clear()
    _CACHE_STATS.update(hits=0, misses=0)


# ---------------------------------------------------------------------------
# Backward routing (DESIGN.md §15): fused kernel bwd vs the oracle VJP
# ---------------------------------------------------------------------------

_BWD_ROUTE_CACHE: dict = {}


def select_bwd_mode(m: int, n: int, k: int, *, dtype: str = "bfloat16",
                    epilogue=None, prologue=None,
                    chip: Optional[pm.ChipSpec] = None) -> str:
    """Route ``gemm_fused(bwd_mode='auto')`` per shape bucket: 'kernel'
    (the fused chain-transpose launches) or 'reference' (the jnp-oracle
    recompute VJP).

    The decision comes from :func:`perf_model.gemm_bwd_route_model` — a
    roofline comparison of the two paths plus a peak-memory residency
    penalty on the kernel path's saved preactivations. Train-shaped cells
    (k ≳ 1024) keep the kernel path; degenerate cells (tiny contraction
    dim, so saved preacts dominate the traffic) route to the oracle.
    Memoized per (pow2-bucketed m, n, k, dtype, chain); the decision is
    journaled as a ``bwd_route`` plan decision so tests audit it without
    monkeypatching.
    """
    if chip is None:
        chip = active_chip()
    m, n, k = int(m), int(n), int(k)
    m_bucket = 1 << max(0, (m - 1).bit_length())  # batch-like dim
    key = (m_bucket, n, k, str(dtype), _chain_str(epilogue),
           _chain_str(prologue), chip.name, _PRETUNED["gen"])
    hit = _BWD_ROUTE_CACHE.get(key)
    if hit is not None:
        if obs.enabled():
            obs.plan_decision("bwd_route", "gemm_bwd", (m, n, k),
                              str(dtype), {"mode": hit, "cached": True},
                              cached=True)
        return hit
    db = _DTYPE_BYTES.get(str(dtype), 2)
    n_saved = 0
    preact_bytes = db
    gated = bool(getattr(epilogue, "gate", False))
    if epilogue is not None and getattr(epilogue, "needs_saved_preact",
                                        False):
        n_saved = int(getattr(epilogue, "saved_accumulators", 1))
        if getattr(epilogue, "preact_keeps_f32", False):
            preact_bytes = 4
    prenorm = bool(prologue is not None
                   and not getattr(prologue, "is_identity", True))
    route = pm.gemm_bwd_route_model(m=m_bucket, n=n, k=k, dtype_bytes=db,
                                    n_saved=n_saved,
                                    preact_bytes=preact_bytes,
                                    gated=gated, prenorm=prenorm, chip=chip)
    mode = route["route"]
    _BWD_ROUTE_CACHE[key] = mode
    obs.plan_decision(
        "bwd_route", "gemm_bwd", (m, n, k), str(dtype),
        {"mode": mode, "kernel_score": route["kernel_score"],
         "reference_score": route["reference_score"],
         "peak_save_bytes": route["peak_save_bytes"]},
        [{"mode": "kernel", "time_s": route["kernel_time_s"],
          "score": route["kernel_score"], "chosen": mode == "kernel"},
         {"mode": "reference", "time_s": route["reference_time_s"],
          "score": route["reference_score"],
          "chosen": mode == "reference"}])
    return mode


# ---------------------------------------------------------------------------
# Fusion-plan selection (DESIGN.md §9): fused vs unfused, from dma_bytes only
# ---------------------------------------------------------------------------

_PLAN_CACHE: dict = {}


def select_fusion(kind: str, shape, dtype="bfloat16", *,
                  residual: bool = True, prenorm: str = "none",
                  backward: bool = False,
                  causal: bool = False, softcap: bool = False,
                  sink: bool = False, shard=None,
                  chip: Optional[pm.ChipSpec] = None) -> dict:
    """Pick the fused or unfused execution plan for a model-layer chain.

    ``chip=None`` resolves against :func:`active_chip` (the calibrated
    ChipSpec when a pretuned table is installed), and an installed table
    pins the fused/unfused DECISION for cells it carries (the byte models
    still fill in the returned plan dict) — see docs/autotuning.md.

    The decision is made *purely* by comparing the two plans' modeled HBM
    traffic (``perf_model.mlp_chain_model`` / ``qkv_rope_chain_model`` /
    ``attention_chain_model``) — no hard-coded preference: a chain that
    stops saving bytes (tiny token counts, residual-free expert FFNs near
    the crossover) loses the selection. Memoized per shape-bucket (the
    token/batch dim rounds to the next power of two).

    ``kind``/``shape``:
      'mlp'       (tokens, d_model, d_ff, gated); ``residual`` says whether
                  the chain ends in a residual add (False for MoE experts)
      'qkv_rope'  (tokens, d_model, num_heads, num_kv_heads, head_dim)
      'qkv'       same shape as 'qkv_rope' but rope-free (BERT/Whisper/
                  enc-dec blocks): the fused side is the packed QK/V GEMM
                  pair with the pre-norm folded in; without a prenorm the
                  plans tie on bytes and 'unfused' wins (the rope-free
                  fusion pays only via the folded norm)
      'attention' (batch, heads, kv_heads, seq_q, seq_kv, head_dim); the
                  fused side is the flash kernel (online softmax, O(1)
                  score memory), the unfused side materializes the
                  (seq_q, seq_kv) score matrix per pass.  ``causal`` /
                  ``softcap`` / ``sink`` describe the epilogue chain the
                  launch runs (softcap adds unfused passes; the sink row
                  is a per-head scalar stream on both sides)

    ``prenorm`` ('rmsnorm' | 'layernorm') prepends the pre-norm of the
    transformer block to both plans: the fused plan folds it into the first
    GEMM's A-tile prologue (DESIGN.md §10), the unfused plan runs the
    standalone norm pass in front of the eager chain.

    ``shard`` (a :class:`~repro.distributed.sharding.ShardSpec`, DESIGN.md
    §16) makes the decision sharding-aware: the spec joins the memo /
    pretuned keys, the chain's collective rides both plans as an
    interconnect term priced from the ICI roofline and folded into
    ``dma_bytes`` in HBM-equivalent units (the ranking stays bytes-only),
    and the returned plan carries ``collective_bytes`` / ``collective_s`` /
    ``overlap_fraction`` for the chosen side. ``shape`` stays the per-rank
    LOCAL chain shape. The extra kind ``'gemm_collective'`` (shape
    (m, n, k), full logical GEMM; requires a shard with an all_gather or
    reduce_scatter collective) scores the ring-overlapped collective GEMM
    against the gather-then-GEMM baseline
    (``perf_model.collective_gemm_model``).

    ``backward=True`` scores the chain's *training backward* instead
    (DESIGN.md §11): the fused side is the kernel-side chain transpose
    (saved-preact streams + two fused bwd GEMM launches per fwd GEMM, norm
    transposed tile-wise; for attention, the saved-(out, lse) flash
    backward), the unfused side is the oracle-recompute VJP (autodiff of
    the unfused jnp chain with full fwd re-materialization).

    Returns {plan: 'fused'|'unfused', fused_bytes, unfused_bytes,
    traffic_reduction, fused: <model dict>, unfused: <model dict>}.
    """
    if chip is None:
        chip = active_chip()
    dtype = str(dtype)
    shape = tuple(int(x) for x in shape)
    tokens = 1 << max(0, (shape[0] - 1).bit_length())  # pow2 bucket
    key = (kind, (tokens,) + shape[1:], dtype, bool(residual), prenorm,
           bool(backward), bool(causal), bool(softcap), bool(sink),
           shard, chip.name, _PRETUNED["gen"])
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        if obs.enabled():
            audit = _PLAN_AUDIT.get(key)
            if audit is not None:
                obs.plan_decision("fusion", kind, shape, dtype,
                                  audit["chosen"], audit["candidates"],
                                  cached=True)
        return hit
    pinned_plan = None
    table = _PRETUNED["table"]
    if table is not None:
        fkey = pretuned_fusion_key(kind, (tokens,) + shape[1:], dtype,
                                   residual=bool(residual), prenorm=prenorm,
                                   backward=bool(backward),
                                   causal=bool(causal),
                                   softcap=bool(softcap), sink=bool(sink),
                                   shard=shard)
        cell = (table.get("fusion") or {}).get(fkey)
        if cell is None:
            obs.incr("autotune.pretuned_fusion_miss")
        elif cell.get("plan", {}).get("plan") in ("fused", "unfused"):
            pinned_plan = cell["plan"]["plan"]
            obs.incr("autotune.pretuned_fusion_hit")
    db = _DTYPE_BYTES.get(dtype, 2)
    if kind == "mlp":
        _, d, f, gated = shape
        model = pm.mlp_chain_bwd_model if backward else pm.mlp_chain_model
        variants = [model(tokens=tokens, d_model=d, d_ff=f,
                          dtype_bytes=db, gated=bool(gated),
                          residual=residual, prenorm=prenorm,
                          fused=fused, chip=chip)
                    for fused in (True, False)]
    elif kind in ("qkv_rope", "qkv"):
        _, d, h, hkv, hd = shape
        model = (pm.qkv_rope_chain_bwd_model if backward
                 else pm.qkv_rope_chain_model)
        variants = [model(tokens=tokens, d_model=d,
                          num_heads=h, num_kv_heads=hkv,
                          head_dim=hd, dtype_bytes=db,
                          prenorm=prenorm, rope=(kind == "qkv_rope"),
                          fused=fused, chip=chip)
                    for fused in (True, False)]
    elif kind == "attention":
        _, h, hkv, sq, skv, hd = shape
        model = (pm.attention_chain_bwd_model if backward
                 else pm.attention_chain_model)
        variants = [model(batch=tokens, heads=h, kv_heads=hkv,
                          seq_q=sq, seq_kv=skv, head_dim=hd,
                          causal=causal, softcap=softcap, sink=sink,
                          dtype_bytes=db, fused=fused, chip=chip)
                    for fused in (True, False)]
    elif kind == "gemm_collective":
        if shard is None or getattr(shard, "collective", "none") not in \
                ("all_gather", "reduce_scatter"):
            raise ValueError(
                "gemm_collective needs a ShardSpec with an all_gather or "
                f"reduce_scatter collective, got shard={shard!r}")
        _, n, k = shape
        variants = [pm.collective_gemm_model(
                        m=tokens, n=n, k=k, n_shards=shard.n_shards,
                        dtype_bytes=db, variant=shard.collective,
                        fused=fused, chip=chip)
                    for fused in (True, False)]
    else:
        raise ValueError(f"unknown fusion kind {kind!r}")
    if (shard is not None and kind != "gemm_collective"
            and getattr(shard, "collective", "none") != "none"):
        # the §16 interconnect term: the chain's collective rides BOTH
        # plans (the wire bytes are plan-invariant for a given sharding —
        # the plans differ on HBM traffic), priced from the ICI roofline
        # and folded into dma_bytes in HBM-equivalent units so the
        # decision below stays bytes-only. all_to_all chains (expert
        # dispatch) pay the wire twice: out and back.
        act_bytes = tokens * shape[1] * db
        if shard.collective == "all_to_all":
            act_bytes *= 2
        variants = [pm.collective_chain_model(
                        v, collective=shard.collective, nbytes=act_bytes,
                        n_shards=shard.n_shards, chip=chip)
                    for v in variants]
    fused, unfused = variants
    plan = dict(
        plan=("fused" if fused["dma_bytes"] < unfused["dma_bytes"]
              else "unfused"),
        fused_bytes=fused["dma_bytes"], unfused_bytes=unfused["dma_bytes"],
        traffic_reduction=unfused["dma_bytes"] / max(1, fused["dma_bytes"]),
        fused=fused, unfused=unfused)
    if pinned_plan is not None:
        # the measured table pins the decision; the byte models above still
        # fill in the plan dict every caller reads
        plan["plan"] = pinned_plan
        plan["pretuned"] = True
    if shard is not None:
        chosen = fused if plan["plan"] == "fused" else unfused
        plan.update(shard=_shard_str(shard),
                    collective_bytes=chosen.get("collective_bytes", 0),
                    collective_s=chosen.get("collective_s", 0.0),
                    overlap_fraction=chosen.get("overlap_fraction", 0.0))
    _PLAN_CACHE[key] = plan
    audit = {"chosen": {"plan": plan["plan"],
                        "traffic_reduction": plan["traffic_reduction"],
                        "prenorm": prenorm, "backward": bool(backward),
                        **({"shard": plan["shard"],
                            "overlap_fraction": plan["overlap_fraction"]}
                           if shard is not None else {}),
                        **({"pretuned": True} if pinned_plan else {})},
             "candidates": [
                 {"plan": "fused", "dma_bytes": plan["fused_bytes"],
                  "chosen": plan["plan"] == "fused"},
                 {"plan": "unfused", "dma_bytes": plan["unfused_bytes"],
                  "chosen": plan["plan"] == "unfused"}]}
    _PLAN_AUDIT[key] = audit
    obs.plan_decision("fusion", kind, shape, dtype,
                      audit["chosen"], audit["candidates"])
    return plan


# ---------------------------------------------------------------------------
# Model-level resolution (used by models/api, dryrun, serve, trainer)
# ---------------------------------------------------------------------------

def policies_for_model(cfg, *, batch: int, seq_len: int,
                       dtype: Optional[str] = None,
                       decode_len: Optional[int] = None,
                       shard=None) -> dict:
    """Resolve the kernel policies a model built from ``cfg`` will use for a
    (batch, seq_len) bucket. Returns {op_kind: KernelPolicy}; attention-free
    architectures get only the 1-D policies.

    ``decode_len`` is the KV-cache slot count of the decode step (an engine
    passes its max_len); the split-KV decode policy resolves against it.
    Windowed layers keep a smaller ring cache and re-resolve their exact
    shape through the same memoized autotuner at trace time.

    ``shard`` (ShardSpec) additionally warms + journals the SHARDED fusion
    plans this bucket will execute (the per-rank MoE expert chain and the
    prenorm-MLP chain with the interconnect term), so a training run's
    plan audit shows the sharded decisions at pin time rather than deep in
    the first traced step."""
    dtype = dtype or getattr(cfg, "compute_dtype", "bfloat16")
    h = getattr(cfg, "num_heads", 0)
    d = getattr(cfg, "head_dim", 0) or 0
    dm = getattr(cfg, "d_model", 0)
    out = {}
    kinds = set(getattr(cfg, "block_pattern", ("attn",)))
    has_attn = bool(kinds & {"attn", "local", "moe"}) or \
        getattr(cfg, "family", "lm") in ("encdec", "vlm")
    if has_attn and h and d:
        attn_shape = (batch, h, seq_len, seq_len, d)
        out["attention_fwd"] = select_policy("attention_fwd", attn_shape,
                                             dtype, causal=True)
        out["attention_bwd"] = select_policy("attention_bwd", attn_shape,
                                             dtype, causal=True)
        hkv = getattr(cfg, "num_kv_heads", h) or h
        out["attention_decode"] = select_policy(
            "attention_decode",
            (batch, hkv, h // hkv, decode_len or seq_len, d), dtype)
        if getattr(cfg, "rope_style", "none") != "none":
            out["rope"] = select_policy("rope", (batch, h, seq_len, d), dtype)
    if dm:
        out["fused_norm"] = select_policy("fused_norm",
                                          (batch * seq_len, dm), dtype)
    d_ff = getattr(cfg, "d_ff", 0) or 0
    if dm and d_ff:
        # The fused-MLP megakernel GEMMs (DESIGN.md §9-§10): the dual-output
        # gated up-projection (with the pre-norm folded into its A prologue
        # when the chain model picks that plan) and the residual-fused
        # down-projection. (Function-level import; epilogue/prologue depend
        # only on jax, so this does not create a core -> kernels cycle.)
        from repro.kernels.gemm.epilogue import Epilogue
        from repro.kernels.gemm.prologue import norm_prologue
        gated = getattr(cfg, "mlp_act", "swiglu") in ("swiglu", "geglu")
        act = "gelu" if getattr(cfg, "mlp_act", "") in ("geglu", "gelu") \
            else "silu"
        tokens = batch * seq_len
        up_ep = (Epilogue(activation=act, gate=True) if gated
                 else Epilogue(activation=act))
        norm_kind = getattr(cfg, "norm", "rmsnorm")
        up_pro = None
        if select_fusion("mlp", (tokens, dm, d_ff, gated), dtype,
                         prenorm=norm_kind)["plan"] == "fused":
            up_pro = norm_prologue(norm_kind, beta=(norm_kind == "layernorm"))
        try:
            out["gemm_mlp_up"] = select_policy("gemm", (tokens, d_ff, dm),
                                               dtype, epilogue=up_ep,
                                               prologue=up_pro)
        except ValueError:
            # full-K A tile doesn't fit VMEM: the model layer falls back to
            # the standalone-norm plan, so report that policy here too
            out["gemm_mlp_up"] = select_policy("gemm", (tokens, d_ff, dm),
                                               dtype, epilogue=up_ep)
        out["gemm_mlp_down"] = select_policy(
            "gemm", (tokens, dm, d_ff), dtype,
            epilogue=Epilogue(residual=True, scale=True))
        if shard is not None:
            # the sharded plans this bucket executes (DESIGN.md §16): the
            # residual-free per-rank expert chain for MoE configs, the
            # plain prenorm chain otherwise — journaled at pin time
            ns = max(1, shard.n_shards)
            if getattr(cfg, "moe", None) is not None:
                loc_f = d_ff if shard.collective == "all_to_all" \
                    else max(1, d_ff // ns)
                select_fusion("mlp", (tokens, dm, loc_f, gated), dtype,
                              residual=False, shard=shard)
            else:
                select_fusion("mlp", (tokens, dm, d_ff, gated), dtype,
                              prenorm=norm_kind, shard=shard)
    return out


def describe_policies(policies: dict) -> dict:
    """JSON-able {op: describe()} for dryrun/report cells."""
    return {op: pol.describe() for op, pol in sorted(policies.items())}
