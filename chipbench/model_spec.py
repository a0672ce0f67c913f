"""A configuration file of ``configs/`` read into the sizes every model
family has; every other key stays in ``raw`` for the family to read
(``families/<family>.py``, named by the file's ``family`` key, "dense"
when it has none)."""
from __future__ import annotations

import dataclasses
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    family: str
    layers: int
    d: int
    heads: int
    kv_heads: int
    hd: int
    vocab: int
    tied: bool
    dtype: str              # weights as held
    compute_dtype: str
    raw: dict = dataclasses.field(compare=False)
    root: str = dataclasses.field(default=ROOT, compare=False)

    @property
    def vocab_padded(self) -> int:
        m = self.raw.get("vocab_pad_multiple", 0)
        return -(-self.vocab // m) * m if m else self.vocab


def from_dict(c: dict, root: str = ROOT) -> ModelSpec:
    """``root``: the tree whose ``chipbench/families/`` holds the family."""
    d = c["hidden_size"]
    return ModelSpec(
        name=c["name"], family=c.get("family", "dense"),
        layers=c["num_hidden_layers"], d=d,
        heads=c["num_attention_heads"], kv_heads=c["num_key_value_heads"],
        hd=c.get("head_dim", d // c["num_attention_heads"]),
        vocab=c["vocab_size"], tied=bool(c["tie_word_embeddings"]),
        dtype=c["dtype"], compute_dtype=c.get("compute_dtype", c["dtype"]),
        raw=c, root=root)


def load(path: str, root: str = ROOT) -> ModelSpec:
    with open(path) as fh:
        return from_dict(json.load(fh), root)


def family(spec: ModelSpec):
    """The module of the spec's family, ``families/<family>.py`` under the
    spec's tree."""
    from .spec import family as find
    return find(spec.root, spec.family)
