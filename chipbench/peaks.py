"""Peak rates of each chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). A kind not in the table is an
error, not a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # bytes/s
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9, "Google Cloud, 'TPU v5e'"),
}


def for_kind(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(PEAKS)}") from None
