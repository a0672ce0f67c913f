"""The trace reduction on two small traces recorded on one TPU v5e by
``tools/record_fixture.py`` (granite-8b at one layer serving three
requests; minicpm-2b at one layer, one training step at 1024 tokens)."""
import os

import pytest

from chipbench import flops, peaks, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
V5E = peaks.for_kind("TPU v5 lite")


@pytest.fixture(scope="module")
def serve():
    return trace_reduce.reduce(os.path.join(DATA, "serve.xplane.pb.gz"))


@pytest.fixture(scope="module")
def train():
    return trace_reduce.reduce(os.path.join(DATA, "train.xplane.pb.gz"))


def test_window_busy_and_idle(serve, train):
    assert serve.devices == train.devices == 1
    assert serve.window_s == pytest.approx(0.107194418, abs=1e-9)
    assert serve.busy_s == pytest.approx(0.049894685, abs=1e-9)
    assert train.window_s == pytest.approx(0.049614206, abs=1e-9)
    assert train.busy_s == pytest.approx(0.045677993, abs=1e-9)
    # gaps are inside the window and no longer than its idle time
    idle = serve.window_s - serve.busy_s
    assert sum(g for _, g in serve.gaps) <= idle + 1e-9
    assert serve.gaps[0][1] >= serve.gaps[-1][1] > 0


def test_kernel_calls_found_by_name(serve, train):
    assert len(serve.kernel_calls("_gemm_pallas")) == 26
    assert len(serve.kernel_calls("flash_decode_paged")) == 9
    assert {k: len(train.kernel_calls(k)) for k in (
        "_gemm_pallas", "_flash_fwd", "_gemm_bwd_da", "_gemm_bwd_db",
        "_flash_bwd")} == {"_gemm_pallas": 4, "_flash_fwd": 1,
                           "_gemm_bwd_da": 4, "_gemm_bwd_db": 4,
                           "_flash_bwd": 2}


def test_one_gemm_call_by_hand(serve):
    call = next(op for op in serve.kernel_calls("_gemm_pallas")
                if op.text.startswith("%_gemm_pallas.9 "))
    outs, ins = trace_reduce.call_shapes(call.text)
    assert outs == [("bf16", (512, 1024))]
    f, b = flops.kernel_call("_gemm_pallas", outs, ins)
    # (512, 4096) @ (4096, 1024) with a (1, 4096) norm scale
    assert f == 2 * 512 * 4096 * 1024
    assert b == (512 * 1024 + 512 * 4096 + 4096 * 1024 + 4096) * 2
    t, bound = flops.least_time(f, b, V5E)
    assert bound == "compute" and t == pytest.approx(21.802e-6, rel=1e-4)
    assert call.dur_ns == 38056.0


def test_flash_backward_roofline_by_hand(train):
    # dK/dV: 3 causal products of 2*36*64*1024*1025/2 FLOPs, compute bound;
    # dQ: 1 product, bound by its 23.9 MB of operands and output
    share, bound = flops.kernel_roofline(train, ["_flash_bwd"], V5E)
    one = 2 * 36 * 64 * 1024 * 1025 / 2
    mb = 36 * 1024 * 64 * 2
    dq_bytes = 5 * mb + 2 * 36 * 1024 * 4
    least = 3 * one / V5E.flops + dq_bytes / V5E.hbm_bw
    spent = sum(op.dur_ns for op in train.kernel_calls("_flash_bwd")) * 1e-9
    assert share == pytest.approx(100 * least / spent, rel=1e-9)
    assert share == pytest.approx(12.601166578, rel=1e-6)


def test_rooflines_stay_under_100(serve, train):
    for t, names in ((serve, ["_gemm_pallas"]),
                     (train, ["_gemm_bwd_da", "_gemm_bwd_db"]),
                     (train, ["_gemm_pallas"]), (train, ["_flash_fwd"])):
        share, _ = flops.kernel_roofline(t, names, V5E)
        assert 0 < share <= 100


def test_breakdown_lists(serve):
    top = serve.top_ops(10)
    assert len(top) == 10 and top[0][0].startswith("flash_decode_paged")
    assert all(isinstance(s, float) for _, s in top + serve.gaps)
    assert not any(n.startswith("while ") for n, _ in top)
