"""A configuration file of ``configs/`` read into the sizes the benchmark
uses; the one place that knows the file's keys."""
from __future__ import annotations

import dataclasses
import json
import math


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    layers: int
    d: int
    f: int
    heads: int
    kv_heads: int
    hd: int
    vocab: int
    tied: bool
    rope_theta: float
    eps: float
    emb_mult: float
    res_mult: float
    logit_div: float
    dtype: str              # weights as held
    compute_dtype: str
    raw: dict = dataclasses.field(compare=False)

    @property
    def vocab_padded(self) -> int:
        m = self.raw.get("vocab_pad_multiple", 0)
        return -(-self.vocab // m) * m if m else self.vocab


def from_dict(c: dict) -> ModelSpec:
    d = c["hidden_size"]
    published = c.get("published_num_hidden_layers", c["num_hidden_layers"])
    res = (c["scale_depth"] / math.sqrt(published)
           if "scale_depth" in c else 1.0)
    return ModelSpec(
        name=c["name"], layers=c["num_hidden_layers"], d=d,
        f=c["intermediate_size"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"],
        hd=c.get("head_dim", d // c["num_attention_heads"]),
        vocab=c["vocab_size"], tied=bool(c["tie_word_embeddings"]),
        rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        emb_mult=float(c.get("scale_emb", 1.0)), res_mult=res,
        logit_div=(d / c["dim_model_base"] if "dim_model_base" in c
                   else 1.0),
        dtype=c["dtype"], compute_dtype=c.get("compute_dtype", c["dtype"]),
        raw=c)


def load(path: str) -> ModelSpec:
    with open(path) as fh:
        return from_dict(json.load(fh))
