"""The GEMM backward kernels (dA and dB) against their roofline."""
from chipbench import flops


def read(run):
    r = flops.kernel_roofline(run.trace, ["_gemm_bwd_da", "_gemm_bwd_db"],
                              run.peak) if run.trace else None
    return r[0] if r else None
