"""Production mesh builders.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state. The single-pod mesh is 16x16 = 256 chips (v5e pod),
('data', 'model'); the multi-pod mesh is 2x16x16 = 512 chips with a leading
'pod' axis that composes with 'data' for hierarchical data parallelism
(DESIGN.md §6).
"""
from __future__ import annotations

import math

import jax


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the GSPMD semantics the
    sharding rules in this repo are written for (JAX 0.9 defaults new
    meshes to ``Explicit`` axes, under which an embedding gather of a
    vocab-sharded table by data-sharded ids no longer resolves)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the {'multi' if multi_pod else 'single'}"
            f"-pod mesh, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(model_axis: int = 1):
    """Debug mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))
