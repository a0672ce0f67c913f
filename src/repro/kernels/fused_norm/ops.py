"""Public fused dropout+residual+layernorm op with mode dispatch."""
from __future__ import annotations

from repro.core.policy import KernelPolicy
from repro.kernels.modes import interpret_for
from .kernel import fused_dropout_residual_layernorm
from .ref import fused_dropout_residual_layernorm_ref


def dropout_residual_layernorm(x, residual, weight, bias, seed=0, *,
                               policy: KernelPolicy | None = None,
                               dropout_p: float = 0.0, eps: float = 1e-5,
                               mode: str = "pallas_interpret"):
    """Fuses prenorm-transformer glue: (dropout(x) + residual) -> LN.

    Returns (normed, new_residual). Shapes: x/residual (rows, d). The row
    block comes from ``policy`` (or the autotuner when None — the memoized
    1-D row-block selection, DESIGN.md §5).
    """
    interpret = interpret_for(mode)
    if mode == "reference":
        return fused_dropout_residual_layernorm_ref(
            x, residual, weight, bias, seed, dropout_p=dropout_p, eps=eps)
    return fused_dropout_residual_layernorm(
        x, residual, weight, bias, seed, policy=policy, dropout_p=dropout_p,
        eps=eps, interpret=interpret)
