"""The kernel dispatch modes every public op accepts.

* ``reference``        — the jnp oracle, no Pallas kernel;
* ``pallas_interpret`` — the Pallas kernels in the interpreter (CPU);
* ``pallas_tpu``       — the Pallas kernels compiled by Mosaic for the TPU.
"""
from __future__ import annotations

from repro import obs

MODES = ("reference", "pallas_interpret", "pallas_tpu")


def check_mode(mode: str) -> None:
    """An unknown mode raises rather than selecting a path."""
    if mode not in MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; have {MODES}")


def interpret_for(mode: str) -> bool:
    """Whether an op called with ``mode`` runs its kernel in the Pallas
    interpreter (an unknown mode raises). Each interpreted launch bumps the
    ``kernels.interpret_launch`` counter, so a capture on the chip can
    assert that none happened."""
    check_mode(mode)
    if mode != "pallas_interpret":
        return False
    obs.incr("kernels.interpret_launch")
    return True
