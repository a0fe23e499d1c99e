"""``flops.py`` against hand counts."""

import json
import os

import pytest

from benchmark import flops, harness


def _cfg(name):
    with open(os.path.join(harness.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, gflop_per_token", [("pythia_1b", 4.64), ("pythia_1.4b", 6.66)])
def test_train_flops_per_token_hand_counts(name, gflop_per_token):
    assert flops.train_flops_per_token(_cfg(name), 2048, lora_r=128) / 1e9 == pytest.approx(gflop_per_token, abs=0.005)


def test_parameter_counts_of_pythia_1b():
    cfg = _cfg("pythia_1b")
    mm = flops.matmul_params(cfg)
    assert (mm["body"] + mm["head"]) / 1e6 == pytest.approx(908.3, abs=0.05)
    assert mm["head"] / 1e6 == pytest.approx(103.0, abs=0.05)
    assert flops.lora_params(cfg, 128) / 1e6 == pytest.approx(67.1, abs=0.05)


def test_full_rank_training_is_six_times_parameters_plus_attention():
    cfg = _cfg("pythia_1b")
    mm = flops.matmul_params(cfg)
    want = 6 * (mm["body"] + mm["head"]) + 6 * 16 * 2048 * 2048
    assert flops.train_flops_per_token(cfg, 2048, lora_r=0) == want


def test_serve_span_is_the_sum_of_its_tokens():
    cfg = _cfg("pythia_1.4b")
    assert flops.serve_flops_span(cfg, 100, 164) == pytest.approx(
        sum(flops.serve_flops_per_token(cfg, p) for p in range(100, 164)), rel=1e-12
    )
    assert flops.serve_flops_span(cfg, 7, 7) == 0


def test_flash_attention_counts_seven_products_and_twelve_tensors():
    cfg = _cfg("pythia_1b")
    w = flops.flash_attention_train(cfg, batch=4, seq=2048)
    assert w["flops"] == 16 * 7 * 4 * 2048 * 2048 * 2048
    assert w["bytes"] == 16 * 12 * 2 * 4 * 2048 * 2048
    assert flops.scaled(w, 5)["flops"] == 5 * w["flops"]
