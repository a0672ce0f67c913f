"""The system under test, as the benchmark drives it: the program's model
built from a configuration file, and the benchmark's weights placed in the
program's parameter tree. The only module here that knows that tree."""
from __future__ import annotations

import jax

from .model_spec import ModelSpec


def model_config(spec: ModelSpec, *, smoke_max_len: int | None = None):
    from repro.configs.base import ModelConfig
    kw = {}
    if spec.raw.get("vocab_pad_multiple"):
        kw["vocab_pad_multiple"] = spec.raw["vocab_pad_multiple"]
    return ModelConfig(
        name=spec.name, family="lm", num_layers=spec.layers, d_model=spec.d,
        num_heads=spec.heads, num_kv_heads=spec.kv_heads, d_ff=spec.f,
        vocab_size=spec.vocab, head_dim=spec.hd, mlp_act="swiglu",
        norm="rmsnorm", tie_embeddings=spec.tied, rope_theta=spec.rope_theta,
        emb_scale=spec.emb_mult, residual_scale=spec.res_mult,
        logit_scale_div=spec.logit_div, param_dtype=spec.dtype,
        compute_dtype=spec.compute_dtype,
        max_seq_len=smoke_max_len or 32768, **kw)


def build(spec: ModelSpec, mode: str):
    from repro.models import build_model
    return build_model(model_config(spec), mode=mode)


# program parameter path -> the benchmark's leaf name (weights.shapes)
CANONICAL = {
    "embed": "embed", "final_norm_scale": "final_norm", "lm_head": "lm_head",
    "blocks/attn/wqk": "wqk", "blocks/attn/wv": "wv", "blocks/attn/wo": "wo",
    "blocks/ln1_scale": "ln1", "blocks/ln2_scale": "ln2",
    "blocks/mlp/w_in": "w_up", "blocks/mlp/w_gate": "w_gate",
    "blocks/mlp/w_out": "w_down",
}


def canonical(tree: dict) -> dict:
    """A tree shaped like the program's parameters, by the benchmark's
    leaf names."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(k, "key", k)) for k in path)
        out[CANONICAL[key]] = leaf
    return out


def program_params(spec: ModelSpec, w: dict, model) -> dict:
    """The benchmark's weights ``w`` in the program's tree (the same
    arrays, no copy), checked leaf by leaf against the model's own
    declaration of shapes and dtypes."""
    blocks = {"attn": {"wqk": w["wqk"], "wv": w["wv"], "wo": w["wo"]},
              "ln1_scale": w["ln1"], "ln2_scale": w["ln2"],
              "mlp": {"w_in": w["w_up"], "w_gate": w["w_gate"],
                      "w_out": w["w_down"]}}
    embed = w["embed"]
    pad = spec.vocab_padded - spec.vocab
    if pad:
        embed = jax.numpy.pad(embed, ((0, pad), (0, 0)))
    params = {"embed": embed, "blocks": blocks,
              "final_norm_scale": w["final_norm"]}
    if not spec.tied:
        head = w["lm_head"]
        params["lm_head"] = (jax.numpy.pad(head, ((0, 0), (0, pad)))
                             if pad else head)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        model.abstract())
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), params)
    if want != got:
        raise ValueError(f"{spec.name}: the program declares {want}, the "
                         f"benchmark's weights map to {got}")
    return params
