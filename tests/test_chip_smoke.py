"""chip_smoke.py off the chip: its phases at tiny sizes with interpreted
kernels (every comparison runs; only the TPU-only checks fail), and its
refusal to report a result without a TPU."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
import repro.configs as configs  # noqa: E402

TPU_ONLY = ("kernel launches were interpreted", "no tpu_custom_call")


@pytest.fixture
def tiny(monkeypatch):
    full = configs.get_config

    def get_config(name, smoke=False):
        if name == cs.SERVE_ARCH:
            return full(name, smoke=True)
        return dataclasses.replace(full(name), num_layers=2, d_model=128,
                                   num_heads=2, num_kv_heads=2, head_dim=64,
                                   d_ff=256, vocab_size=512)

    monkeypatch.setattr(configs, "get_config", get_config)
    monkeypatch.setattr(cs, "serve_depth", lambda cfg, limit: 2)
    for name, value in dict(KERNEL_MODE="pallas_interpret", SERVE_REQUESTS=3,
                            SERVE_PROMPT_LENS=(16, 24, 32),
                            SERVE_NEW_TOKENS=4, PAGE_SIZE=16, TRAIN_BATCH=4,
                            TRAIN_SEQ=64, TRAIN_STEPS=2).items():
        monkeypatch.setattr(cs, name, value)
    monkeypatch.setattr(cs, "FAILURES", [])
    return cs


def _failures_off_chip(cs):
    return [f for f in cs.FAILURES if not any(t in f for t in TPU_ONLY)]


def test_serve_phase_compares_engine_logits(tiny):
    out = tiny.serve_phase(jax.devices()[0], 0, tiny.CompileClock())
    assert _failures_off_chip(tiny) == []
    assert out["prefill_err"] < 0.05 and out["decode_err"] < 0.05
    assert out["agree"] >= 0.5


def test_train_phase_compares_gradients(tiny):
    out = tiny.train_phase(jax.devices()[0], 0, tiny.CompileClock())
    assert _failures_off_chip(tiny) == []
    assert out["grad_worst"] <= tiny.GRAD_MAX_ERR
    assert out["loss_diff"] <= tiny.LOSS_ATOL


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
