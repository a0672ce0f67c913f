"""Unified model API: one object per architecture family.

``build_model(cfg)`` returns a :class:`Model` whose methods close over the
kernel mode / mesh, giving every arch the same surface:
  init, param_defs, loss, forward, init_cache, prefill, decode_step,
  make_batch (ShapeDtypeStructs OR real random arrays for a given ShapeConfig)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core import autotune
from repro.kernels.modes import check_mode
from . import lm as _lm
from . import encdec as _ed
from . import vlm as _vlm
from .common import abstract_params, init_params, logical_axes


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    defs: dict
    loss: Callable            # (params, batch) -> (loss, metrics)
    forward: Callable         # (params, batch-or-tokens) -> (logits, aux)
    init_cache: Callable      # (batch, max_len) -> cache
    prefill: Callable         # (params, batch, cache) -> (cache, logits)
    decode_step: Callable     # (params, token, cache, pos) -> (cache, logits)
    # paged decode surface (decoder-only LM/VLM backbones; DESIGN.md §8):
    #   init_paged_cache(batch_slots, n_pages, page_size) -> cache
    #   prefill_paged(params, tokens, cache, page_rows, slot, true_len)
    #   prefill_paged_chunk(params, tokens, cache, page_rows, start,
    #                       last_index) — chunked/suffix prefill (§14)
    #   decode_step_paged(params, token, cache, page_table, lengths)
    #     (token (B, T): T > 1 is the speculative verify step)
    init_paged_cache: Optional[Callable] = None
    prefill_paged: Optional[Callable] = None
    prefill_paged_chunk: Optional[Callable] = None
    decode_step_paged: Optional[Callable] = None
    # {op: KernelPolicy} resolved at build time for the config's default
    # bucket — inspectable summary of what the kernels will do; exact
    # (batch, seq) buckets re-resolve via the memoized autotuner cache
    # (serve/engine and train/trainer pin those).
    default_policies: dict = dataclasses.field(default_factory=dict)

    def init(self, rng) -> dict:
        return init_params(self.defs, rng)

    # ---- kernel policies -----------------------------------------------
    def resolve_policies(self, shape: Optional[ShapeConfig] = None,
                         *, batch: int = 1,
                         seq_len: Optional[int] = None) -> dict:
        """Resolve (and warm the autotuner cache with) the KernelPolicies
        this model's kernels will use for a (batch, seq) bucket. Called at
        model-build time with the config's max shape; callers with a known
        bucket (dryrun cells, serve buckets, trainer) re-resolve exactly.
        Returns {op_kind: KernelPolicy}."""
        if shape is not None:
            batch, seq_len = shape.global_batch, shape.seq_len
        seq_len = seq_len if seq_len is not None else \
            min(self.cfg.max_seq_len, 4096)
        return autotune.policies_for_model(self.cfg, batch=batch,
                                           seq_len=seq_len)

    def abstract(self) -> dict:
        return abstract_params(self.defs)

    def axes(self) -> dict:
        return logical_axes(self.defs)

    # ---- batch construction --------------------------------------------
    def batch_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct inputs for the dry-run (no allocation)."""
        return make_batch(self.cfg, shape, abstract=True)

    def make_batch(self, shape: ShapeConfig, rng) -> dict:
        return make_batch(self.cfg, shape, abstract=False, rng=rng)


def make_batch(cfg: ModelConfig, shape: ShapeConfig, *, abstract: bool,
               rng=None) -> dict:
    """Inputs for train ({'inputs','targets','loss_mask', frontends...})."""
    b, s = shape.global_batch, shape.seq_len
    out: dict[str, Any] = {}

    def toks(shp):
        if abstract:
            return jax.ShapeDtypeStruct(shp, jnp.int32)
        return jax.random.randint(rng, shp, 0, cfg.vocab_size, jnp.int32)

    def arr(shp):
        if abstract:
            return jax.ShapeDtypeStruct(shp, jnp.dtype(cfg.compute_dtype))
        return jax.random.normal(rng, shp, jnp.dtype(cfg.compute_dtype))

    if cfg.family == "encdec":
        out["encoder_embeds"] = arr((b, cfg.encoder_seq, cfg.d_model))
        out["inputs"] = toks((b, s))
        out["targets"] = toks((b, s))
    elif cfg.family == "vlm":
        p = cfg.num_patches
        out["patch_embeds"] = arr((b, p, cfg.d_model))
        out["inputs"] = toks((b, s - p))
        out["targets"] = toks((b, s - p))
    else:
        out["inputs"] = toks((b, s))
        out["targets"] = toks((b, s))
    mask_shape = out["targets"].shape
    out["loss_mask"] = (jax.ShapeDtypeStruct(mask_shape, jnp.float32)
                        if abstract else jnp.ones(mask_shape, jnp.float32))
    return out


def build_model(cfg: ModelConfig, *, mode: Optional[str] = None, mesh=None,
                data_axes=("data",)) -> Model:
    """Build the model. For kernel modes, also resolve the config's default
    bucket into :attr:`Model.default_policies` — an inspectable summary of
    the tiling strategy; launch-time callers (serve buckets, trainer steps)
    re-resolve their exact (batch, seq) buckets through the same memoized
    autotuner, so this is a preview, not the binding choice."""
    model = _build_model(cfg, mode=mode, mesh=mesh, data_axes=data_axes)
    if mode not in (None, "reference"):
        model.default_policies = model.resolve_policies()
    return model


def _build_model(cfg: ModelConfig, *, mode: Optional[str] = None, mesh=None,
                 data_axes=("data",)) -> Model:
    mode = mode if mode is not None else "reference"
    check_mode(mode)
    if mode == "pallas_tpu" and mesh is not None and mesh.size > 1:
        # the dense layers call the kernels under GSPMD, and Mosaic kernels
        # cannot be partitioned automatically (only shard_map'd ones can)
        raise ValueError("mode='pallas_tpu' needs a single-device mesh; "
                         "Mosaic kernels cannot be partitioned by GSPMD")
    kw = dict(mode=mode, mesh=mesh, data_axes=data_axes)

    if cfg.family == "encdec":
        defs = _ed.encdec_param_defs(cfg)
        return Model(
            cfg=cfg, defs=defs,
            loss=functools.partial(_ed.encdec_loss, cfg, **kw),
            forward=functools.partial(_ed.encdec_forward, cfg, **kw),
            init_cache=functools.partial(_ed.encdec_init_cache, cfg),
            prefill=functools.partial(_ed.encdec_prefill, cfg, mode=mode),
            decode_step=functools.partial(_ed.encdec_decode_step, cfg,
                                          mode=mode, mesh=mesh,
                                          data_axes=data_axes),
        )
    if cfg.family == "encoder":
        from . import encoder as _enc

        def _no_decode(*a, **k):
            raise NotImplementedError("encoder-only archs have no decode step")

        defs = _enc.encoder_param_defs(cfg)
        return Model(
            cfg=cfg, defs=defs,
            loss=functools.partial(_enc.encoder_loss, cfg, **kw),
            forward=functools.partial(_enc.encoder_forward, cfg, **kw),
            init_cache=_no_decode, prefill=_no_decode, decode_step=_no_decode,
        )
    if cfg.family == "vlm":
        defs = _vlm.vlm_param_defs(cfg)

        def vlm_prefill(params, batch, cache):
            # prepend patch embeds by running lm_prefill over combined tokens
            raise NotImplementedError(
                "vlm serving uses text-only prefill on the LM backbone")

        return Model(
            cfg=cfg, defs=defs,
            loss=functools.partial(_vlm.vlm_loss, cfg, **kw),
            forward=functools.partial(_vlm.vlm_forward, cfg, **kw),
            init_cache=functools.partial(_lm.lm_init_cache, cfg),
            prefill=lambda params, batch, cache: _lm.lm_prefill(
                cfg, params,
                batch["inputs"] if isinstance(batch, dict) else batch,
                cache, **kw),
            decode_step=functools.partial(_lm.lm_decode_step, cfg, **kw),
            init_paged_cache=functools.partial(_lm.lm_init_paged_cache, cfg),
            prefill_paged=functools.partial(_lm.lm_prefill_paged, cfg, **kw),
            prefill_paged_chunk=functools.partial(_lm.lm_prefill_paged_chunk,
                                                  cfg, **kw),
            decode_step_paged=functools.partial(_lm.lm_decode_step_paged,
                                                cfg, **kw),
        )

    defs = _lm.lm_param_defs(cfg)
    return Model(
        cfg=cfg, defs=defs,
        loss=functools.partial(_lm.lm_loss, cfg, **kw),
        forward=lambda params, batch, **k: _lm.lm_forward(
            cfg, params,
            batch["inputs"] if isinstance(batch, dict) else batch, **kw, **k),
        init_cache=functools.partial(_lm.lm_init_cache, cfg),
        prefill=lambda params, tokens, cache: _lm.lm_prefill(
            cfg, params,
            tokens["inputs"] if isinstance(tokens, dict) else tokens,
            cache, **kw),
        decode_step=functools.partial(_lm.lm_decode_step, cfg, **kw),
        init_paged_cache=functools.partial(_lm.lm_init_paged_cache, cfg),
        prefill_paged=functools.partial(_lm.lm_prefill_paged, cfg, **kw),
        prefill_paged_chunk=functools.partial(_lm.lm_prefill_paged_chunk,
                                              cfg, **kw),
        decode_step_paged=functools.partial(_lm.lm_decode_step_paged,
                                            cfg, **kw),
    )
