"""Paper Fig. 9-style memory-bound decode sweep: split-KV kernel vs einsum.

Sweeps seq_len × batch × GQA ratio at q_len = 1 — the regime where the
paper's wins are largest (1.2-2.4×, memory-bound + GQA). Per DESIGN.md §7:
``us_per_call`` measures the jitted einsum reference decode on XLA-CPU
(scale only); ``derived`` carries the modeled v5e numbers — the split-KV
policy the autotuner picks, its achieved-bandwidth fraction, and the
modeled speedup over a no-split launch (one grid cell per (batch, kv_head),
which under-occupies the DMA pipeline exactly when batch × kv_heads is
small — the split-KV story). A paged-layout row shows the split of whole
pages that the paged policy derives, against the tuned contiguous split.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core import perf_model as pm
from repro.kernels.attention import attention_decode, resolve_decode_policy
from .common import measure_cell, emit


def _modeled(b, hkv, group, skv, d, block_kv):
    return pm.decode_step_model(batch=b, kv_heads=hkv, group=group,
                                kv_len=skv, head_dim=d, block_kv=block_kv)


def _row(name, b, h, hkv, skv, d, *, page_size=None):
    group = h // hkv
    rng = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(rng[0], (b, h, 1, d), jnp.float32)
    k = jax.random.normal(rng[1], (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(rng[2], (b, hkv, skv, d), jnp.float32)
    lengths = jnp.full((b,), skv, jnp.int32)

    fn = jax.jit(lambda q, k, v: attention_decode(q, k, v, lengths,
                                                  mode="reference"))
    us = measure_cell(fn, q, k, v)["us"]

    if page_size is None:
        pol = autotune.select_policy("attention_decode",
                                     (b, hkv, group, skv, d))
        block_kv = pol.block_kv
    else:
        block_kv = resolve_decode_policy(b, hkv, group, skv, d, q.dtype,
                                         page_size=page_size).block_kv
    tuned = _modeled(b, hkv, group, skv, d, block_kv)
    nosplit = _modeled(b, hkv, group, skv, d, skv)
    emit(name, us,
         f"modeled_v5e_us={tuned['time_s'] * 1e6:.1f};"
         f"block_kv={block_kv};n_splits={tuned['n_splits']};"
         f"bw_frac={tuned['achieved_bw'] / pm.V5E.hbm_bw:.2f};"
         f"split_speedup={nosplit['time_s'] / tuned['time_s']:.2f}x")


def main() -> None:
    smoke = bool(os.environ.get("BENCH_SMOKE"))
    if smoke:
        seqs, batches, groups, h, d = (128, 256), (1, 2), (1, 4), 4, 16
    else:
        seqs, batches, groups, h, d = (512, 2048, 4096), (1, 4), (1, 8), 8, 64
    for skv in seqs:
        for b in batches:
            for group in groups:
                hkv = h // group
                _row(f"decode_s{skv}_b{b}_g{group}", b, h, hkv, skv, d)
    # paged layout: the split is a block of whole pages
    skv, b, group = seqs[-1], batches[0], groups[-1]
    page = 64 if smoke else 256
    _row(f"decode_paged_s{skv}_b{b}_g{group}_p{page}", b, h, h // group,
         skv, d, page_size=page)


if __name__ == "__main__":
    main()
