"""Public RoPE op with mode dispatch + custom VJP.

RoPE is linear in x and the rotation is orthogonal, so the VJP is simply the
rotation by −θ — the same kernel with negated sin (run under the same policy:
the cotangent has the forward's shape, so the forward's tuned block applies).
"""
from __future__ import annotations

import functools

import jax

from repro.core.policy import KernelPolicy
from repro.kernels.modes import interpret_for
from .kernel import rope_pallas
from .ref import rope_ref, rope_tables  # noqa: F401


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rope(x, sin, cos, policy, interpret):
    return rope_pallas(x, sin, cos, policy=policy, interpret=interpret)


def _rope_fwd(x, sin, cos, policy, interpret):
    return rope_pallas(x, sin, cos, policy=policy, interpret=interpret), (sin, cos)


def _rope_bwd(policy, interpret, res, g):
    sin, cos = res
    return rope_pallas(g, -sin, cos, policy=policy, interpret=interpret), None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope(x, sin, cos, *, policy: KernelPolicy | None = None,
         mode: str = "pallas_interpret"):
    """Apply rotary embedding. x: (B, H, S, D); sin/cos: (S, D)."""
    interpret = interpret_for(mode)
    if mode == "reference":
        return rope_ref(x, sin, cos)
    return _rope(x, sin, cos, policy, interpret)
