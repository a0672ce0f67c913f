"""Model weights made from the seed, on the device, in one jitted call.

The layout is the benchmark's own, independent of the program's: the
leaves every family has,

    embed (V, d), lm_head (d, V) unless tied, final_norm (d,)

and the family's stacked per-layer leaves (``families/<family>.py``'s
``shapes``), each with the layers as its first axis and any rank after it.

Matrices are normal with std 1/sqrt(fan-in), the fan-in being the
second-to-last side of one layer's slice (d for a (d, f) matrix, an
(E, d, f) expert stack or a (d, E) router); an untied embedding has unit
std, a tied one 1/sqrt(d) (it is also the LM head); norm scales are
1 + 0.1 * normal, so a path that drops a norm's scale shows. Each stacked
leaf is drawn one layer at a time, so no leaf ever holds an int32 copy of
itself. The same seed gives the same weights on the same device.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import model_spec

UNSTACKED = ("embed", "final_norm", "lm_head")


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole number (beyond 32 bits too)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def common(spec: model_spec.ModelSpec) -> dict:
    """The leaves every family has: {name: (shape, kind)}."""
    out = {"embed": ((spec.vocab, spec.d), "embed"),
           "final_norm": ((spec.d,), "norm")}
    if not spec.tied:
        out["lm_head"] = ((spec.d, spec.vocab), "matrix")
    return out


def shapes(spec: model_spec.ModelSpec) -> dict:
    """{name: (shape, kind)}; kind is 'matrix', 'embed' or 'norm'."""
    return model_spec.family(spec).shapes(spec)


def _draw(key, shape, kind, dtype, spec):
    if kind == "norm":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if kind == "embed":
        std = spec.d ** -0.5 if spec.tied else 1.0
    else:
        std = shape[-2] ** -0.5
    return (jax.random.normal(key, shape, dtype) * std).astype(dtype)


def leaf(spec, name: str, key, dtype):
    """One leaf; a stacked leaf is drawn layer by layer under lax.map."""
    table = shapes(spec)
    shape, kind = table[name]
    k = jax.random.fold_in(key, sorted(table).index(name))
    if name in UNSTACKED:
        return _draw(k, shape, kind, dtype, spec)
    layer_keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(
        jnp.arange(shape[0]))
    return jax.lax.map(lambda lk: _draw(lk, shape[1:], kind, dtype, spec),
                       layer_keys)


def make(spec: model_spec.ModelSpec, seed: int, dtype=None) -> dict:
    """Every leaf, in ``dtype`` (default: the dtype the config states)."""
    dtype = jnp.dtype(dtype or spec.dtype)
    names = sorted(shapes(spec))
    fn = jax.jit(lambda key: {n: leaf(spec, n, key, dtype) for n in names})
    return fn(seed_key(seed))
