#!/usr/bin/env python3
"""On-chip smoke check: the serving and training paths on one TPU.

  python3 chip_smoke.py              # one chip: serve + train phases
  python3 chip_smoke.py --chips 4    # four chips: the cross-chip paths only

Serve: ``granite-8b`` at its published widths, depth cut so that the
weights as the engine holds them (bf16) plus the KV pool take at most
``SERVE_MEMORY_SHARE`` of the chip's memory, served through the launcher's
own ``PagedEngine`` path with the ``pallas_tpu`` kernels and again with the
``reference`` ops on the same chip. The engine's ``logits_hook`` records
the logits every emitted token was sampled from: the prefill logits of the
engine's paged prefill and those of every paged decode step. A float32
reference (``reference`` ops at float32 compute and highest matmul
precision, same weights) runs teacher-forced over each engine's own tokens.
Prefill and decode logits are gated separately, by relative L2 norm: the
kernel engine must be no further from float32 than ``LOGITS_ERR_SLACK``
times the bf16 reference engine, and within ``LOGITS_MAX_ERR``. Its tokens
must agree with the float32 argmax on the same context at least as often as
the reference engine's do, less ``TOKEN_AGREE_SLACK``.

Train: ``llama-100m`` (the paper's validation model, full size) through
``train_loop`` for ``TRAIN_STEPS`` steps at batch 8, seq 1024 with both
modes from the same initialisation and data: losses finite and within
``LOSS_ATOL`` nats. The gradients of the first batch are compared per
parameter leaf against float32 gradients: the worst leaf's relative L2
error of the kernel path must be within ``GRAD_ERR_SLACK`` times the bf16
reference's and within ``GRAD_MAX_ERR``, and the script checks that this
limit lies below what a step that lost half the batch reads.

Both phases assert that every kernel family the launch journal recorded is
a ``tpu_custom_call`` in the programs the engine and the trainer compiled,
and that no kernel ran in the Pallas interpreter. Every check runs; any
failure exits non-zero.

With ``--chips 4`` the script runs only a data x model mesh step of
``llama-100m`` (``reference`` ops: Mosaic kernels cannot be partitioned by
GSPMD, so the model refuses ``pallas_tpu`` on a multi-device mesh), its
losses and per-leaf gradients against one chip, and the ring collective
GEMM with the ``pallas_tpu`` kernels under ``shard_map`` against
gather-then-GEMM (bitwise) and a plain float32 dot.

Weights and data come from ``--seed``. Without a TPU the script exits
non-zero and prints no result; its last line otherwise is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
KERNEL_MODE = "pallas_tpu"

SERVE_ARCH = "granite-8b"
SERVE_MEMORY_SHARE = 0.6   # weights + KV pool; the rest is headroom for the
                           # reference pool, activations and the LM head cast
SERVE_REQUESTS = 8
SERVE_PROMPT_LENS = (128, 256, 512)
SERVE_NEW_TOKENS = 32
PAGE_SIZE = 64
LOGITS_ERR_SLACK = 1.5     # kernel error over bf16-reference error
LOGITS_MAX_ERR = 0.5       # |engine - float32|_2 / |float32|_2
TOKEN_AGREE_SLACK = 0.1    # share of tokens equal to the float32 argmax

TRAIN_ARCH = "llama-100m"
TRAIN_STEPS = 5
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
LOSS_ATOL = 1e-2           # nats, per step
GRAD_ERR_SLACK = 1.5       # worst-leaf kernel error over bf16-reference's
GRAD_MAX_ERR = 0.15        # worst leaf |g - g_f32|_2 / |g_f32|_2

RING_SHAPE = (4096, 4096, 4096)   # (M, K, N) of the collective GEMM
RING_RTOL = 1e-4                  # kernels vs a float32 dot of bf16 inputs

# journal op -> the jit scope that names its pallas_call in compiled HLO
KERNEL_SCOPES = {
    "gemm": "_gemm_pallas", "gemm_fused": "_gemm_pallas",
    "gemm_bwd_da": "_gemm_bwd_da", "gemm_bwd_db": "_gemm_bwd_db",
    "attention_fwd": "_flash_fwd", "attention_bwd": "_flash_bwd",
    "attention_decode": "flash_decode", "rope": "jit(_rope)",
    "fused_norm": "jit(_fused)",
}

FAILURES: list = []


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> bool:
    """Record a failed check and go on, so one run shows every reading."""
    if not ok:
        FAILURES.append(msg)
        log(f"FAILED: {msg}")
    return bool(ok)


class CompileClock:
    """Seconds of XLA compilation (or persistent-cache reads, which take its
    place), summed from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def custom_call_scopes(compiled) -> list:
    """op_name of every tpu_custom_call in a compiled program."""
    import re
    return [m.group(1) for line in compiled.as_text().splitlines()
            if "tpu_custom_call" in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def footprint(compiled) -> int:
    """Device bytes a compiled program holds at once, by the compiler's own
    memory analysis (arguments + outputs + temporaries - aliased)."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def check_kernels(rec, programs: list, phase: str) -> dict:
    """Every journaled kernel family is a tpu_custom_call in ``programs``
    (the phase's compiled programs); none ran interpreted."""
    launches = rec.launch_counts()
    interp = int(rec.counter("kernels.interpret_launch"))
    log(f"[{phase}] journal launches {launches}; interpret launches {interp}")
    fallbacks = {k: v for k, v in rec.counters.items()
                 if k.startswith("fallback.")}
    for k in ("model.standalone_norm", "moe.collective_mode_fallback"):
        fallbacks[k] = rec.counter(k)
    log(f"[{phase}] plan fallbacks {fallbacks}")
    check(interp == 0, f"{phase}: {interp} kernel launches were interpreted")
    check(launches, f"{phase}: no kernel was launched")
    scopes = [s for c in programs for s in custom_call_scopes(c)]
    found = {}
    for op in launches:
        scope = KERNEL_SCOPES[op]
        found[op] = sum(scope in s for s in scopes)
        check(found[op], f"{phase}: no tpu_custom_call for {op} ({scope})")
    log(f"[{phase}] tpu_custom_call per family in {len(programs)} compiled "
        f"programs {found}; largest program footprint "
        f"{max(footprint(c) for c in programs)} B (compiler memory analysis)")
    return launches


def memory(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_limit", "bytes_in_use",
                                      "peak_bytes_in_use")}


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def rel_l2(a, b) -> float:
    import numpy as np
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def served_config(cfg, depth: int):
    """``cfg`` at ``depth`` layers with its weights held in bf16, as the
    engine holds them on one chip; widths stay published."""
    return dataclasses.replace(cfg, num_layers=depth,
                               param_dtype=cfg.compute_dtype)


def serve_depth(cfg, bytes_limit: int) -> int:
    """Largest depth whose bf16 weights plus KV pool fit the share."""
    from repro.models import build_model
    from repro.models.common import param_bytes

    def weights(layers):
        return param_bytes(build_model(served_config(cfg, layers)).defs)

    per_layer = weights(2) - weights(1)
    rest = weights(1) - per_layer
    longest = max(SERVE_PROMPT_LENS) + SERVE_NEW_TOKENS
    pages = SERVE_REQUESTS * -(-longest // PAGE_SIZE) + 1
    kv_per_layer = (2 * pages * PAGE_SIZE * cfg.num_kv_heads * cfg.head_dim
                    * 2)
    depth = int((SERVE_MEMORY_SHARE * bytes_limit - rest)
                // (per_layer + kv_per_layer))
    return min(depth, cfg.num_layers)


def engine_programs(engine) -> list:
    """The engine's own compiled buckets, lowered at the shapes it ran them
    (with a persistent cache these are cache reads of the same programs)."""
    import jax
    import jax.numpy as jnp

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            tree)

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, weak_type=True)
    params, cache = sds(engine.params), sds(engine.cache)
    out = []
    for key, entry in engine._buckets.items():
        if "decode" in entry:                       # (batch_slots, pages)
            b, pages = key
            args = (params, i32(b, 1), cache, i32(b, pages), i32(b))
            out.append(entry["decode"].lower(*args).compile())
        if "prefill" in entry:                      # ("prefill", length)
            args = (params, i32(1, key[1]), cache,
                    i32(engine.max_pages_per_seq), scalar, scalar)
            out.append(entry["prefill"].lower(*args).compile())
    return out


def teacher_forced(model, params, tokens: dict, plens: dict, n: int):
    """Logits ``model`` gives for positions plen .. plen+n-1 of each uid's
    ``tokens`` (the rows its greedy tokens are the argmax of), fed the
    tokens themselves: {uid: (n, V) float32}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    uids = sorted(tokens)
    width = max(plens[u] + n - 1 for u in uids)
    toks = np.zeros((len(uids), width), np.int32)
    rows = np.zeros((len(uids), n), np.int32)
    for i, u in enumerate(uids):
        seq = tokens[u][:plens[u] + n - 1]
        toks[i, :len(seq)] = seq
        rows[i] = plens[u] - 1 + np.arange(n)
    fn = jax.jit(lambda p, t, r: jnp.take_along_axis(
        model.forward(p, t)[0], r[..., None], axis=1))
    with jax.default_matmul_precision("highest"):
        out = np.asarray(fn(params, jnp.asarray(toks), jnp.asarray(rows)),
                         np.float32)
    return {u: out[i] for i, u in enumerate(uids)}


def serve_phase(dev, seed: int, clock: CompileClock) -> dict:
    import jax
    import numpy as np
    from repro import obs
    from repro.configs import get_config
    from repro.launch.serve import make_requests, serve
    from repro.models import build_model

    base = get_config(SERVE_ARCH)
    depth = serve_depth(base, memory(dev)["bytes_limit"])
    cfg = served_config(base, depth)
    log(f"[serve] {SERVE_ARCH}: d={cfg.d_model} heads={cfg.num_heads}/"
        f"{cfg.num_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}; depth {depth} of {base.num_layers}")
    kernels = build_model(cfg, mode=KERNEL_MODE)
    params = kernels.init(jax.random.PRNGKey(seed))
    requests = make_requests(cfg.vocab_size, SERVE_REQUESTS,
                             SERVE_PROMPT_LENS, SERVE_NEW_TOKENS, seed)
    plens = {r.uid: len(r.prompt) for r in requests}
    log(f"[serve] {len(requests)} requests, prompt lengths "
        f"{list(plens.values())}, {SERVE_NEW_TOKENS} new tokens each, greedy")

    runs = {}
    for name, model in ((KERNEL_MODE, kernels),
                        ("reference", build_model(cfg, mode="reference"))):
        rows: dict = {}

        def hook(uid, pos, row, rows=rows):
            rows[(uid, pos)] = np.asarray(row, np.float32)

        c0, t0 = clock.seconds, time.perf_counter()
        with obs.capture() as rec:
            engine = serve(model, params, requests,
                           batch_slots=SERVE_REQUESTS, page_size=PAGE_SIZE,
                           logits_hook=hook)
        wall = time.perf_counter() - t0
        compile_s = clock.seconds - c0
        report = engine.report()
        log(f"[serve:{name}] weights {tree_bytes(params)} B, KV pool "
            f"{tree_bytes(engine.cache)} B; device memory {memory(dev)} "
            f"(peak is process-wide)")
        log(f"[serve:{name}] compile {compile_s:.1f} s, run "
            f"{wall - compile_s:.1f} s, wall {wall:.1f} s; engine {report}")
        if name == KERNEL_MODE:
            check_kernels(rec, engine_programs(engine), "serve")
        check(report["completed"] == len(requests), f"{name}: {report}")
        logits = {u: np.stack([rows[(u, p + j)]
                               for j in range(SERVE_NEW_TOKENS)])
                  for u, p in plens.items()}
        check(all(np.isfinite(v).all() for v in logits.values()),
              f"{name}: non-finite logits")
        runs[name] = dict(logits=logits, tokens=dict(engine.results))
        del engine

    f32 = build_model(dataclasses.replace(cfg, compute_dtype="float32"),
                      mode="reference")
    out = {}
    for name, run in runs.items():
        truth = teacher_forced(f32, params, run["tokens"], plens,
                               SERVE_NEW_TOKENS)
        got = run["logits"]
        uids = sorted(plens)
        pre = rel_l2(np.stack([got[u][0] for u in uids]),
                     np.stack([truth[u][0] for u in uids]))
        dec = rel_l2(np.stack([got[u][1:] for u in uids]),
                     np.stack([truth[u][1:] for u in uids]))
        emitted = np.stack([run["tokens"][u][plens[u]:] for u in uids])
        agree = emitted == np.stack([truth[u].argmax(-1) for u in uids])
        out[name] = dict(prefill=pre, decode=dec, agree=float(agree.mean()),
                         first_agree=int(agree[:, 0].sum()),
                         first=emitted[:, 0].tolist(), emitted=emitted)
        log(f"[serve:{name}] |engine - float32|_2 / |float32|_2: prefill "
            f"{pre:.5f}, decode {dec:.5f}; tokens equal to the float32 "
            f"argmax on their own context {int(agree.sum())} of "
            f"{agree.size}, first tokens {int(agree[:, 0].sum())} of "
            f"{len(uids)}; first tokens {emitted[:, 0].tolist()}")

    k, r = out[KERNEL_MODE], out["reference"]
    for part in ("prefill", "decode"):
        log(f"[serve] {part} logits error kernel {k[part]:.5f} vs bf16 "
            f"reference {r[part]:.5f} (limits {LOGITS_ERR_SLACK} x "
            f"reference, {LOGITS_MAX_ERR})")
        check(k[part] <= LOGITS_ERR_SLACK * r[part],
              f"{part} logits: kernel error {k[part]} above "
              f"{LOGITS_ERR_SLACK} x reference {r[part]}")
        check(k[part] <= LOGITS_MAX_ERR,
              f"{part} logits: kernel error {k[part]} above {LOGITS_MAX_ERR}")
    log(f"[serve] share of tokens equal to the float32 argmax: kernel "
        f"{k['agree']:.4f} vs bf16 reference {r['agree']:.4f} (limit "
        f"reference - {TOKEN_AGREE_SLACK})")
    check(k["agree"] >= r["agree"] - TOKEN_AGREE_SLACK,
          f"kernel tokens agree with float32 for {k['agree']}, reference "
          f"for {r['agree']}")
    same_first = sum(a == b for a, b in zip(k["first"], r["first"]))
    common = [int(np.argmin(np.append(a == b, False)))
              for a, b in zip(k["emitted"], r["emitted"])]
    log(f"[serve] kernel and reference engines: same first token for "
        f"{same_first} of {len(common)} requests; tokens in common before "
        f"they diverge {common}")
    return {"depth": depth, "prefill_err": k["prefill"],
            "decode_err": k["decode"], "agree": k["agree"],
            "reference": {p: r[p] for p in ("prefill", "decode", "agree")}}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_data(model, seed: int, mesh=None):
    from repro.data.pipeline import DataConfig, DataIterator
    return DataIterator(DataConfig(vocab_size=model.cfg.vocab_size,
                                   seq_len=TRAIN_SEQ,
                                   global_batch=TRAIN_BATCH, seed=seed),
                        mesh=mesh)


def train_losses(model, seed: int, *, mesh=None):
    """``train_loop`` losses, per-step seconds from the trainer spans, the
    journal, and the trainer's compiled step program."""
    import jax
    from repro import obs
    from repro.optim import AdamWConfig, constant_schedule
    from repro.train import abstract_state, make_train_step, train_loop

    opt = AdamWConfig(schedule=constant_schedule(3e-4))
    with obs.capture() as rec:
        res = train_loop(model, train_data(model, seed, mesh), TRAIN_STEPS,
                         opt, rng=jax.random.PRNGKey(seed), mesh=mesh,
                         log=lambda *a, **k: None)
    step_s = [s.dur for s in rec.spans if s.name == "trainer.step"]
    step = None
    if mesh is None:
        batch = next(train_data(model, seed))
        step = make_train_step(model, opt).lower(
            abstract_state(model), batch).compile()
    return res.losses, step_s, rec, step


def leaf_errors(grads, truth) -> dict:
    """{leaf path: |g - truth|_2 / |truth|_2}, computed on the device."""
    import jax
    import jax.numpy as jnp

    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                     1e-30)

    errs = jax.jit(lambda g, t: jax.tree.map(err, g, t))(grads, truth)
    flat, _ = jax.tree_util.tree_flatten_with_path(errs)
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def describe_errors(errs: dict) -> str:
    import numpy as np
    worst = max(errs, key=errs.get)
    return (f"worst {errs[worst]:.5f} ({worst}), median "
            f"{float(np.median(list(errs.values()))):.5f} over {len(errs)} "
            f"leaves")


def first_batch_grads(model, params, seed: int, *, mesh=None, rows=None,
                      highest: bool = False):
    """Gradients of the loss on the trainer's first batch (its first
    ``rows`` rows when given) and the compiled gradient program."""
    import contextlib
    import jax
    from repro.data.pipeline import batch_at, global_batch_at

    dcfg = train_data(model, seed).cfg
    if mesh is not None:
        from repro.train import state_shardings
        batch = global_batch_at(dcfg, 0, mesh)
        p_sh = state_shardings(model, mesh, zero1=False)["params"]
        params = jax.device_put(params, p_sh)
        fn = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]),
                     in_shardings=(p_sh, None), out_shardings=p_sh)
    else:
        batch = batch_at(dcfg, 0)
        if rows is not None:
            batch = {k: v[:rows] for k, v in batch.items()}
        fn = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]))
    ctx = (jax.default_matmul_precision("highest") if highest
           else contextlib.nullcontext())
    with ctx:
        compiled = fn.lower(params, batch).compile()
        return compiled(params, batch), compiled


def float32_model(model):
    from repro.models import build_model
    return build_model(dataclasses.replace(model.cfg,
                                           compute_dtype="float32"),
                       mode="reference")


def grad_controls(model, params, seed: int) -> tuple:
    """float32 gradients of the first batch, and what a step that saw only
    half of it reads against them (the limit must lie below that)."""
    f32 = float32_model(model)
    truth, _ = first_batch_grads(f32, params, seed, highest=True)
    half, _ = first_batch_grads(f32, params, seed, rows=TRAIN_BATCH // 2,
                                highest=True)
    half_errs = leaf_errors(half, truth)
    log(f"[grads] control: float32 gradients of half the batch vs the whole "
        f"batch: {describe_errors(half_errs)}; best leaf "
        f"{min(half_errs.values()):.5f}")
    check(max(half_errs.values()) > GRAD_MAX_ERR,
          f"GRAD_MAX_ERR {GRAD_MAX_ERR} does not separate a half-batch step")
    return truth, half_errs


def check_grads(name: str, errs: dict, control: dict, phase: str) -> None:
    """``errs`` (the path under test) against ``control`` (the bf16
    reference on one chip), both per leaf against float32."""
    worst, limit = max(errs.values()), max(control.values())
    log(f"[{phase}] gradients {name} vs float32: {describe_errors(errs)} "
        f"(limits {GRAD_ERR_SLACK} x reference worst {limit:.5f}, "
        f"{GRAD_MAX_ERR})")
    check(worst <= GRAD_ERR_SLACK * limit,
          f"{phase}: worst-leaf gradient error {worst} above "
          f"{GRAD_ERR_SLACK} x reference {limit}")
    check(worst <= GRAD_MAX_ERR,
          f"{phase}: worst-leaf gradient error {worst} above {GRAD_MAX_ERR}")


def train_phase(dev, seed: int, clock: CompileClock) -> dict:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(TRAIN_ARCH)
    log(f"[train] {TRAIN_ARCH}: {cfg.num_layers} layers, d={cfg.d_model}, "
        f"heads {cfg.num_heads} x {cfg.head_dim}, batch {TRAIN_BATCH}, "
        f"seq {TRAIN_SEQ}, {TRAIN_STEPS} steps")
    losses, models = {}, {}
    for mode in (KERNEL_MODE, "reference"):
        model = models[mode] = build_model(cfg, mode=mode)
        c0 = clock.seconds
        loss, step_s, rec, step = train_losses(model, seed)
        compile_s = clock.seconds - c0
        log(f"[train:{mode}] losses {loss}")
        log(f"[train:{mode}] compile {compile_s:.1f} s; step seconds "
            f"{[round(s, 4) for s in step_s]} (the first includes compile); "
            f"device memory {memory(dev)} (peak is process-wide)")
        losses[mode] = np.asarray(loss)
        if mode == KERNEL_MODE:
            journal = rec
            programs = [step]
    k, r = losses[KERNEL_MODE], losses["reference"]
    check(np.isfinite(k).all(), f"non-finite kernel losses {k}")
    diff = float(np.max(np.abs(k - r)))
    log(f"[train] max |kernel - reference| loss {diff:.6f} nats (limit "
        f"{LOSS_ATOL}); reference loss fell {r[0] - r[-1]:.6f} over the "
        f"steps")
    check(diff <= LOSS_ATOL, f"training losses differ by {diff}")

    params = models["reference"].init(jax.random.PRNGKey(seed))
    truth, _ = grad_controls(models["reference"], params, seed)
    errs = {}
    for mode, model in models.items():
        grads, compiled = first_batch_grads(model, params, seed)
        errs[mode] = leaf_errors(grads, truth)
        if mode == KERNEL_MODE:
            programs.append(compiled)
        del grads
    check_kernels(journal, programs, "train")
    log(f"[train] gradients reference vs float32: "
        f"{describe_errors(errs['reference'])}")
    check_grads(KERNEL_MODE, errs[KERNEL_MODE], errs["reference"], "train")
    return {"loss_diff": diff,
            "grad_worst": max(errs[KERNEL_MODE].values()),
            "grad_worst_reference": max(errs["reference"].values())}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import obs
    from repro.configs import get_config
    from repro.kernels.gemm import gemm_collective_sharded
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.models import build_model

    cfg = get_config(TRAIN_ARCH)
    mesh = make_host_mesh(model_axis=2)
    # reference ops: GSPMD cannot partition the Mosaic kernels, so the
    # model refuses pallas_tpu on a multi-device mesh
    log(f"[mesh] {TRAIN_ARCH} train step (reference ops) on mesh "
        f"{dict(mesh.shape)} vs one chip")
    single = build_model(cfg, mode="reference")
    model = build_model(cfg, mode="reference", mesh=mesh)
    one, _, _, _ = train_losses(single, seed)
    multi, step_s, rec, _ = train_losses(model, seed, mesh=mesh)
    one, multi = np.asarray(one), np.asarray(multi)
    diff = float(np.max(np.abs(multi - one)))
    log(f"[mesh] one-chip losses {one.tolist()}")
    log(f"[mesh] mesh losses {multi.tolist()}; step seconds "
        f"{[round(s, 4) for s in step_s]}; journal {rec.launch_counts()}")
    log(f"[mesh] max |mesh - one chip| loss {diff:.6f} nats (limit "
        f"{LOSS_ATOL})")
    check(np.isfinite(multi).all() and diff <= LOSS_ATOL,
          f"mesh losses differ from one chip by {diff}")

    params = single.init(jax.random.PRNGKey(seed))
    truth, _ = grad_controls(single, params, seed)
    g_one, _ = first_batch_grads(single, params, seed)
    g_mesh, _ = first_batch_grads(model, params, seed, mesh=mesh)
    one_errs = leaf_errors(g_one, truth)
    log(f"[mesh] gradients one chip vs float32: {describe_errors(one_errs)}")
    log(f"[mesh] gradients mesh vs one chip: "
        f"{describe_errors(leaf_errors(g_mesh, g_one))}")
    check_grads("mesh", leaf_errors(g_mesh, truth), one_errs, "mesh")
    del g_one, g_mesh, truth

    ring_mesh = make_mesh((4,), ("model",))
    m, k, n = RING_SHAPE
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, k), jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(seed + 1), (k, n), jnp.bfloat16)
         * k ** -0.5).astype(jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        dot = np.asarray(jnp.dot(x, w, preferred_element_type=jnp.float32))
    for variant in ("all_gather", "reduce_scatter"):
        with obs.capture() as rec:
            ring, gather = (np.asarray(gemm_collective_sharded(
                x, w, mesh=ring_mesh, variant=variant, mode=KERNEL_MODE,
                out_dtype=jnp.float32, plan=plan), np.float32)
                for plan in ("ring", "gather"))
        rel = float(np.abs(ring - dot).max() / np.abs(dot).max())
        log(f"[ring] {variant} {m}x{k}x{n}: ring == gather bitwise "
            f"{np.array_equal(ring, gather)}; max |ring - float32 dot| / "
            f"max |dot| {rel:.3e} (limit {RING_RTOL}); journal "
            f"{rec.launch_counts()}, counters {rec.counters}")
        check(np.array_equal(ring, gather), f"{variant}: ring != gather")
        check(rel <= RING_RTOL, f"{variant}: ring != float32 dot ({rel})")


# ---------------------------------------------------------------------------

def run_phase(name: str, fn, *args):
    """Run one phase; a phase that raises is a failure, the others go on."""
    try:
        return fn(*args)
    except Exception:                                   # noqa: BLE001
        traceback.print_exc()
        check(False, f"{name} phase raised")
        return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py: run it from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.util import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"device {device}; compile cache {cache_dir}")
    if dev.platform != "tpu":
        print("chip_smoke.py: no TPU found", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_phase("mesh", mesh_phase, args.seed)
    else:
        serve = run_phase("serve", serve_phase, dev, args.seed, clock)
        train = run_phase("train", train_phase, dev, args.seed, clock)
        log(f"[summary] serve {serve}; train {train}")
    log(f"[summary] compile {clock.seconds:.1f} s of "
        f"{time.perf_counter() - t0:.1f} s wall; persistent cache hits "
        f"{clock.cache_hits}; process-wide peak device memory "
        f"{memory(dev)['peak_bytes_in_use']} B")
    if FAILURES:
        print(f"chip_smoke.py: {len(FAILURES)} checks failed:",
              *FAILURES, sep="\n  ", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
