"""Work counts against hand counts at granite-8b and minicpm-2b widths."""
import os

import pytest

from chipbench import flops, model_spec, peaks
from chipbench.model_spec import family
from chipbench.tests.tree import REPO


def _spec(name, **kw):
    import dataclasses
    s = model_spec.load(os.path.join(REPO, "chipbench", "configs",
                                     f"{name}.json"))
    return dataclasses.replace(s, **kw)


def test_peaks_by_device_kind():
    p = peaks.for_kind("TPU v5 lite")
    assert (p.flops, p.hbm_bw, p.hbm_bytes) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v4")


def test_granite_matmul_params_and_head():
    g = _spec("granite-8b")
    # 4096 (4096 + 2 1024) + 4096^2 + 3 4096 14336
    assert family(g).matmul_params(g) == 25165824 + 16777216 + 176160768
    assert flops.head_params(g) == 4096 * 49152


def test_minicpm_train_flops_per_token():
    m = _spec("minicpm-2b", layers=6)
    assert family(m).matmul_params(m) == 15925248 + 5308416 + 39813120
    n = 6 * 61046784 + 2304 * 122753
    attn = 3 * 4 * 36 * 64 * 6 * (4097 / 2)
    assert flops.train_flops_per_token(m, 4096) == pytest.approx(
        6 * n + attn, rel=1e-12)
    assert flops.train_flops_per_token(m, 4096) == pytest.approx(
        4234443264, rel=1e-12)


def test_gemm_call_from_hlo_shapes():
    outs = [("bf16", (512, 5120))]
    ins = [("bf16", (512, 4096)), ("bf16", (4096, 5120)),
           ("bf16", (1, 4096)), ("f32", (512, 128)), ("f32", (512, 128))]
    f, b = flops.kernel_call("_gemm_pallas", outs, ins)
    assert f == 2 * 512 * 4096 * 5120
    assert b == 5242880 + 4194304 + 41943040 + 8192 + 524288
    # gated: two weight operands
    f2, _ = flops.kernel_call(
        "_gemm_pallas", [("bf16", (32, 14336))],
        [("bf16", (32, 4096)), ("bf16", (4096, 14336)), ("bf16", (1, 4096)),
         ("bf16", (4096, 14336))])
    assert f2 == 2 * 2 * 32 * 4096 * 14336
    # dB: rows of the first operand times each weight-shaped output
    f3, _ = flops.kernel_call(
        "_gemm_bwd_db", [("bf16", (2304, 5760)), ("bf16", (2304, 5760))],
        [("bf16", (4096, 2304)), ("bf16", (1, 2304)),
         ("bf16", (4096, 5760))])
    assert f3 == 2 * 2 * 4096 * 2304 * 5760


def test_flash_backward_counts_four_products():
    q = ("bf16", (1, 36, 4096, 64))
    one = 2 * 36 * 64 * 4096 * 4097 / 2
    dkdv, _ = flops.kernel_call("_flash_bwd", [q, q], [q] * 4)
    dq, _ = flops.kernel_call("_flash_bwd", [q], [q] * 4)
    assert (dkdv, dq) == (3 * one, one)
    assert dkdv + dq == pytest.approx(154656571392)


def test_paged_attention_decode_and_chunk():
    g = _spec("granite-8b")
    f, b = flops.paged_attention_call(g, [(1, 99, 1)])
    assert f == 4 * 32 * 128 * 100
    assert b == 100 * 2 * 8 * 128 * 2 + 2 * 32 * 128 * 2
    f, _ = flops.paged_attention_call(g, [(512, 1024, 512)])
    assert f == 4 * 32 * 128 * (512 * 1024 + 512 * 513 / 2)


def test_least_time_names_its_bound():
    p = peaks.for_kind("TPU v5 lite")
    assert flops.least_time(197e12, 1.0, p) == (1.0, "compute")
    assert flops.least_time(1.0, 819e9, p) == (1.0, "memory")
