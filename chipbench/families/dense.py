"""The dense decoder family: llama layout (pre-RMSNorm, RoPE, grouped-query
attention, SwiGLU MLP), with MiniCPM's scalings where the configuration
gives them. Everything of the harness that knows this architecture is
here; ``system``, ``weights``, ``reference`` and ``flops`` dispatch to it
by the configuration's ``family`` key ("dense" when absent).

What a family module holds:

    model_config(spec)                  the program's ModelConfig
    shapes(spec)                        leaf name -> (shape, kind)
    program_params(spec, w)             the weights in the program's tree
    canonical(tree)                     a program-shaped tree by leaf name
    hidden(spec, w, tokens, quant, remat), logits(spec, w, h, quant)
                                        the plain reference
    body_params(spec)                   matmul weights a token passes
                                        through in all the layers
    attn_flops(spec, keys)              forward FLOPs of one query row
                                        over ``keys`` keys, all layers
    paged_layers(spec, rows)            [(rows of one layer's paged
                                        attention call, layers making it)]

Keys read from the configuration's ``raw`` besides the common ones:
intermediate_size; rope_theta (10000 when absent, as in the llama
configuration); rms_norm_eps (1e-6); scale_emb, scale_depth with
published_num_hidden_layers, dim_model_base (MiniCPM; 1 when absent);
vocab_pad_multiple.

The reference, in float32 at the highest matmul precision:

    x = embed[tokens] * scale_emb
    per layer: x += r * Wo attn(rope(q), rope(k), v) of rmsnorm(x) * g1
               x += r * Wdown (silu(h Wgate) * (h Wup)), h = rmsnorm(x) * g2
    logits = rmsnorm(x) * g @ head / (d / dim_model_base)
"""
from __future__ import annotations

import dataclasses
import math

import jax

from chipbench import reference, system, weights
from chipbench.reference import attention, mm, rmsnorm, rope


@dataclasses.dataclass(frozen=True)
class Sizes:
    f: int
    rope_theta: float
    eps: float
    emb_mult: float
    res_mult: float
    logit_div: float


def sizes(spec) -> Sizes:
    c = spec.raw
    published = c.get("published_num_hidden_layers", c["num_hidden_layers"])
    return Sizes(
        f=c["intermediate_size"],
        rope_theta=float(c.get("rope_theta", 10000.0)),
        eps=float(c.get("rms_norm_eps", 1e-6)),
        emb_mult=float(c.get("scale_emb", 1.0)),
        res_mult=(c["scale_depth"] / math.sqrt(published)
                  if "scale_depth" in c else 1.0),
        logit_div=(spec.d / c["dim_model_base"] if "dim_model_base" in c
                   else 1.0))


# -- the program --------------------------------------------------------------

def model_config(spec):
    from repro.configs.base import ModelConfig
    s = sizes(spec)
    kw = {}
    if spec.raw.get("vocab_pad_multiple"):
        kw["vocab_pad_multiple"] = spec.raw["vocab_pad_multiple"]
    return ModelConfig(
        name=spec.name, family="lm", num_layers=spec.layers, d_model=spec.d,
        num_heads=spec.heads, num_kv_heads=spec.kv_heads, d_ff=s.f,
        vocab_size=spec.vocab, head_dim=spec.hd, mlp_act="swiglu",
        norm="rmsnorm", tie_embeddings=spec.tied, rope_theta=s.rope_theta,
        emb_scale=s.emb_mult, residual_scale=s.res_mult,
        logit_scale_div=s.logit_div, param_dtype=spec.dtype,
        compute_dtype=spec.compute_dtype, max_seq_len=32768, **kw)


def shapes(spec) -> dict:
    """The benchmark's own layout: wqk (L, d, (H + Hkv) * hd), q then k
    columns; wv (L, d, Hkv * hd); wo (L, H * hd, d); w_gate, w_up
    (L, d, f); w_down (L, f, d); ln1, ln2 (L, d)."""
    d, f, L = spec.d, sizes(spec).f, spec.layers
    q, kv = spec.heads * spec.hd, spec.kv_heads * spec.hd
    return {**weights.common(spec),
            "wqk": ((L, d, q + kv), "matrix"),
            "wv": ((L, d, kv), "matrix"),
            "wo": ((L, q, d), "matrix"),
            "w_gate": ((L, d, f), "matrix"),
            "w_up": ((L, d, f), "matrix"),
            "w_down": ((L, f, d), "matrix"),
            "ln1": ((L, d), "norm"),
            "ln2": ((L, d), "norm")}


# program parameter path -> the benchmark's leaf name
CANONICAL = {
    "embed": "embed", "final_norm_scale": "final_norm", "lm_head": "lm_head",
    "blocks/attn/wqk": "wqk", "blocks/attn/wv": "wv", "blocks/attn/wo": "wo",
    "blocks/ln1_scale": "ln1", "blocks/ln2_scale": "ln2",
    "blocks/mlp/w_in": "w_up", "blocks/mlp/w_gate": "w_gate",
    "blocks/mlp/w_out": "w_down",
}


def canonical(tree: dict) -> dict:
    return system.rename(tree, CANONICAL)


def program_params(spec, w: dict) -> dict:
    blocks = {"attn": {"wqk": w["wqk"], "wv": w["wv"], "wo": w["wo"]},
              "ln1_scale": w["ln1"], "ln2_scale": w["ln2"],
              "mlp": {"w_in": w["w_up"], "w_gate": w["w_gate"],
                      "w_out": w["w_down"]}}
    return {**system.outer_params(spec, w), "blocks": blocks}


# -- the plain reference ------------------------------------------------------

LAYER_KEYS = ("wqk", "wv", "wo", "w_gate", "w_up", "w_down", "ln1", "ln2")


def layer(spec, lw: dict, x, positions, quant=None):
    """One decoder layer on one sequence x (S, d), float32."""
    s = sizes(spec)
    q_w = spec.heads * spec.hd
    h = rmsnorm(x, lw["ln1"], s.eps)
    qk = mm(h, lw["wqk"], quant)
    q = qk[:, :q_w].reshape(-1, spec.heads, spec.hd)
    k = qk[:, q_w:].reshape(-1, spec.kv_heads, spec.hd)
    v = mm(h, lw["wv"], quant).reshape(-1, spec.kv_heads, spec.hd)
    q, k = rope(q, positions, s.rope_theta), rope(k, positions,
                                                  s.rope_theta)
    x = x + s.res_mult * mm(attention(q, k, v, quant), lw["wo"], quant)
    h = rmsnorm(x, lw["ln2"], s.eps)
    up = jax.nn.silu(mm(h, lw["w_gate"], quant)) * mm(h, lw["w_up"], quant)
    return x + s.res_mult * mm(up, lw["w_down"], quant)


def hidden(spec, w: dict, tokens, quant=None, remat=False):
    """Final-normed hidden states of one sequence: tokens (S,) -> (S, d)."""
    s = sizes(spec)
    x = w["embed"][tokens].astype(jax.numpy.float32) * s.emb_mult
    positions = jax.numpy.arange(tokens.shape[0])

    def body(x, lw):
        return layer(spec, lw, x, positions, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, {k: w[k] for k in LAYER_KEYS})
    return rmsnorm(x, w["final_norm"], s.eps)


def logits(spec, w: dict, h, quant=None):
    return mm(h, reference.head(spec, w), quant) / sizes(spec).logit_div


# -- work counts --------------------------------------------------------------

def matmul_params(spec) -> int:
    """Weights of the matmuls of one layer."""
    q, kv = spec.heads * spec.hd, spec.kv_heads * spec.hd
    return spec.d * (q + 2 * kv) + q * spec.d + 3 * spec.d * sizes(spec).f


def body_params(spec) -> int:
    return spec.layers * matmul_params(spec)


def attn_flops(spec, keys: float) -> float:
    return 4.0 * spec.heads * spec.hd * keys * spec.layers


def paged_layers(spec, rows: list) -> list:
    """Every layer's paged call sees the same rows."""
    return [(rows, spec.layers)]
