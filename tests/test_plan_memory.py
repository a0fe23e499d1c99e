"""tools/plan_memory.py — abstract per-device HBM accounting."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_plan(*args):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "plan_memory.py"), *args],
        capture_output=True,
        text=True,
        timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout)


def test_headline_config_fits_v5e():
    out = run_plan("--model", "llama_1b", "--rank", "128", "--micro-batch", "8", "--seq", "1024")
    assert out["fits"] is True
    # measured reality check: this config runs on the chip with ~7GB headroom
    assert 5 < out["per_device_gb"]["total"] < 14


def test_no_remat_matches_measured_oom():
    """Without remat the dense S^2 f32 attention residuals dominate — the
    on-chip compile failed allocating 51.5GB (llama_1b, a record removed in PR 22);
    the estimate must land in the same does-not-fit regime."""
    out = run_plan(
        "--model", "llama_1b", "--rank", "128", "--micro-batch", "8",
        "--seq", "1024", "--remat", "none",
    )
    assert out["fits"] is False
    assert out["per_device_gb"]["activations"] > 16


def test_quantized_base_shrinks_frozen_params():
    full = run_plan("--model", "llama_250m", "--rank", "128")
    nf4 = run_plan("--model", "llama_250m", "--rank", "128", "--quantize", "nf4")
    int8 = run_plan("--model", "llama_250m", "--rank", "128", "--quantize", "int8")
    f, i, n = (
        x["per_device_gb"]["frozen_params"] for x in (full, int8, nf4)
    )
    assert n < i < f
    # nf4 ≈ 1/8 of f32, int8 ≈ 1/4
    assert n < f / 6 and i < f / 3


def test_sharding_divides_params():
    one = run_plan("--model", "llama_1b", "--rank", "0")
    fsdp = run_plan("--model", "llama_1b", "--rank", "0", "--mesh", "fsdp=8")
    # fsdp shards the embed dim of every kernel: frozen+trainable+adam all shrink
    assert (
        fsdp["per_device_gb"]["adam_moments"]
        < one["per_device_gb"]["adam_moments"] / 4
    )
    assert fsdp["devices"] == 8


def test_chunked_loss_removes_logits():
    dense = run_plan("--model", "llama_1b")
    chunked = run_plan("--model", "llama_1b", "--loss", "chunked")
    assert dense["per_device_gb"]["logits"] > 0.5
    assert chunked["per_device_gb"]["logits"] == 0
