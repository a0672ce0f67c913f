"""Model weights made from the seed, on the device, in one jitted call.

The layout is the benchmark's own, independent of the program's:

    embed (V, d), lm_head (d, V) unless tied, final_norm (d,)
    layers: wqk (L, d, (H + Hkv) * hd)   q then k columns
            wv (L, d, Hkv * hd), wo (L, H * hd, d)
            w_gate, w_up (L, d, f), w_down (L, f, d)
            ln1, ln2 (L, d)

Matrices are normal with std 1/sqrt(fan-in); an untied embedding has unit
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


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A threefry key from any whole number (beyond 32 bits too)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def shapes(spec: model_spec.ModelSpec) -> dict:
    """{name: (shape, kind)}; kind is 'matrix', 'embed' or 'norm'."""
    d, f, L = spec.d, spec.f, spec.layers
    q, kv = spec.heads * spec.hd, spec.kv_heads * spec.hd
    out = {
        "embed": ((spec.vocab, d), "embed"),
        "final_norm": ((d,), "norm"),
        "wqk": ((L, d, q + kv), "matrix"),
        "wv": ((L, d, kv), "matrix"),
        "wo": ((L, q, d), "matrix"),
        "w_gate": ((L, d, f), "matrix"),
        "w_up": ((L, d, f), "matrix"),
        "w_down": ((L, f, d), "matrix"),
        "ln1": ((L, d), "norm"),
        "ln2": ((L, d), "norm"),
    }
    if not spec.tied:
        out["lm_head"] = ((d, spec.vocab), "matrix")
    return out


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
    shape, kind = shapes(spec)[name]
    k = jax.random.fold_in(key, sorted(shapes(spec)).index(name))
    if name in ("embed", "final_norm", "lm_head"):
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

