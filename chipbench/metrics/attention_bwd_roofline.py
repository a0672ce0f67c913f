"""The flash attention backward kernels (dK/dV and dQ, causal) against
their roofline."""
from chipbench import flops


def read(run):
    r = flops.kernel_roofline(run.trace, ["_flash_bwd"], run.peak,
                              causal=True) if run.trace else None
    return r[0] if r else None
