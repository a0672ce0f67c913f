"""The Pallas GEMM calls of the serving window against their roofline,
each call's work from the shapes in its HLO text."""
from chipbench import flops


def read(run):
    r = flops.kernel_roofline(run.trace, ["_gemm_pallas"], run.peak) \
        if run.trace else None
    return r[0] if r else None
