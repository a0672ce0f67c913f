"""The work the algorithm needs, counted from shapes: FLOPs (a multiply-add
is 2) and bytes of the operands it must read and the results it must
write, the same whatever implements it. Recomputation is never counted.

Attention is causal: a query at position p attends p + 1 keys.
"""
from __future__ import annotations

from .model_spec import ModelSpec, family


def head_params(spec: ModelSpec) -> int:
    return spec.d * spec.vocab


def attn_flops(spec: ModelSpec, keys) -> float:
    """Forward FLOPs of one query row over ``keys`` keys, all layers."""
    return family(spec).attn_flops(spec, keys)


def train_flops_per_token(spec: ModelSpec, seq: int) -> float:
    """Forward + backward (3x forward) FLOPs per token of a causal
    sequence of ``seq`` tokens: 6 N (layers' matmuls and the LM head) plus
    the attention scores and values at the mean causal context."""
    n = family(spec).body_params(spec) + head_params(spec)
    return 6.0 * n + 3.0 * attn_flops(spec, (seq + 1) / 2)


def serve_step_flops(spec: ModelSpec, decode_ctx, chunks) -> float:
    """One engine step: a decode row per entry of ``decode_ctx`` (keys
    attended) through every layer and the head; each prefill chunk
    (start, tokens) through every layer, and the head for its last row."""
    body = 2.0 * family(spec).body_params(spec)
    head = 2.0 * head_params(spec)
    f = sum(body + head + attn_flops(spec, c) for c in decode_ctx)
    for start, n in chunks:
        f += n * body + head
        f += attn_flops(spec, n * start + n * (n + 1) / 2)
    return f


def paged_attention_call(spec: ModelSpec, rows: list, kv_bytes: int = 2):
    """(FLOPs, bytes) of one layer's paged attention call. ``rows`` holds
    (queries, keys already cached, keys new) per sequence: a decode slot is
    (1, ctx - 1, 1), a chunk (n, start, n). Bytes: every key and value
    attended read once, q read and the output written (the new keys and
    values are written to the cache before the call, by another op)."""
    flops = bytes_ = 0.0
    kv_row = 2 * spec.kv_heads * spec.hd * kv_bytes
    q_row = spec.heads * spec.hd * kv_bytes
    for nq, cached, new in rows:
        keys = cached * nq + nq * (nq + 1) / 2 if nq > 1 else cached + 1
        flops += 4.0 * spec.heads * spec.hd * keys
        bytes_ += (cached + new) * kv_row + 2 * nq * q_row
    return flops, bytes_


def paged_attention_launch(spec: ModelSpec, rows: list, peak) -> float:
    """Least time of one launch's paged attention calls, every layer's:
    the family says which rows each layer's call sees."""
    return sum(n * least_time(*paged_attention_call(spec, r), peak)[0]
               for r, n in family(spec).paged_layers(spec, rows))


def least_time(flops: float, bytes_: float, peak) -> tuple[float, str]:
    tc, tm = flops / peak.flops, bytes_ / peak.hbm_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")


# -- kernel calls, from the shapes in their HLO text -------------------------

def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _matrices(shapes, rows=None) -> list:
    """2-D shapes with both sides > 1 whose first side is not ``rows``."""
    return [s for _, s in shapes
            if len(s) == 2 and min(s) > 1 and s[0] != rows]


def kernel_call(name: str, outs: list, ins: list, *, causal: bool = True):
    """(FLOPs, bytes) of one kernel call from its outputs and operands as
    [(dtype, shape)]; bytes are every operand read and every output
    written once. Returns None for a kernel this table does not know.

    GEMM forward and dA: rows M of the first output times each weight
    operand (a matrix whose rows are not M). dB: rows M of the first
    operand times each weight-shaped output. Flash attention over
    (B, H, S, hd): a causal score or value product is 2 B H hd S(S+1)/2;
    the forward has two, the backward four (dK/dV call: three, dQ call:
    one; the recomputed scores are not counted)."""
    from .trace_reduce import nbytes
    io = nbytes(outs) + nbytes(ins)
    if name in ("_gemm_pallas", "_gemm_bwd_da"):
        m = outs[0][1][0]
        return sum(2.0 * m * _numel(w) for w in _matrices(ins, m)), io
    if name == "_gemm_bwd_db":
        m = ins[0][1][0]
        return sum(2.0 * m * _numel(w) for w in _matrices(outs, m)), io
    if name in ("_flash_fwd", "_flash_bwd"):
        b, h, s, hd = ins[0][1]
        pairs = s * (s + 1) / 2 if causal else s * s
        one = 2.0 * b * h * hd * pairs
        if name == "_flash_fwd":
            return 2 * one, io
        four_d = [o for o in outs if len(o[1]) == 4 and o[0] != "f32"]
        return (3 if len(four_d) == 2 else 1) * one, io
    return None


def kernel_roofline(trace, names, peak, *, causal: bool = True):
    """Share (%) of the least time (the larger of FLOPs / peak and bytes /
    bandwidth, per call) in the device time of the calls of ``names``,
    and which bound holds for most of that least time. None when the
    trace has no such call."""
    from .trace_reduce import call_shapes
    calls = [op for n in names for op in trace.kernel_calls(n)]
    if not calls:
        return None
    least = {"compute": 0.0, "memory": 0.0}
    for op in calls:
        outs, ins = call_shapes(op.text)
        work = kernel_call(op.name, outs, ins, causal=causal)
        if work is None:
            raise ValueError(f"no work function for kernel {op.name}")
        t, bound = least_time(*work, peak)
        least[bound] += t
    spent = sum(op.dur_ns for op in calls) * 1e-9
    return 100.0 * sum(least.values()) / spent, max(least, key=least.get)
