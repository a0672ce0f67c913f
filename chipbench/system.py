"""The system under test, as the benchmark drives it: the program's model
built from a configuration file, and the benchmark's weights placed in the
program's parameter tree. The configuration's family
(``families/<family>.py``) knows the tree; this module dispatches to it
and holds what every family shares."""
from __future__ import annotations

import jax

from .model_spec import ModelSpec, family


def build(spec: ModelSpec, mode: str):
    from repro.models import build_model
    return build_model(family(spec).model_config(spec), mode=mode)


def canonical(spec: ModelSpec, tree: dict) -> dict:
    """A tree shaped like the program's parameters, by the benchmark's
    leaf names."""
    return family(spec).canonical(tree)


def rename(tree: dict, names: dict) -> dict:
    """The leaves of ``tree`` by ``names[<path joined by '/'>]``."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[names[key]] = leaf
    return out


def outer_params(spec: ModelSpec, w: dict) -> dict:
    """The leaves every family shares in the program's tree: the
    embedding and the untied head, padded to the program's vocabulary,
    and the final norm."""
    embed = w["embed"]
    pad = spec.vocab_padded - spec.vocab
    if pad:
        embed = jax.numpy.pad(embed, ((0, pad), (0, 0)))
    params = {"embed": embed, "final_norm_scale": w["final_norm"]}
    if not spec.tied:
        head = w["lm_head"]
        params["lm_head"] = (jax.numpy.pad(head, ((0, 0), (0, pad)))
                             if pad else head)
    return params


def program_params(spec: ModelSpec, w: dict, model) -> dict:
    """The benchmark's weights ``w`` in the program's tree (the same
    arrays, no copy), checked leaf by leaf against the model's own
    declaration of shapes and dtypes."""
    params = family(spec).program_params(spec, w)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        model.abstract())
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if want != got:
        raise ValueError(f"{spec.name}: the program declares {want}, the "
                         f"benchmark's weights map to {got}")
    return params
