"""The ``afmoe`` family's benchmark files at a size a CPU holds: required
operations against hand counts, weights from the seed, ``correct`` true for
the program and false for the control and for each fault a forward can have,
and the metric files this family's cell brought read what they say."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_afmoe, harness, run, weights_afmoe
from benchmark.drivers import serve_afmoe
from benchmark.readers import spans
from benchmark.reference import afmoe as reference

from .conftest import CPU, DATA


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(DATA, "bench_tiny_afmoe.json")) as f:
        return harness.Cell("serve.tiny_afmoe", json.load(f), base=DATA)


def test_flops_against_hand_counts(cell):
    cfg = cell.config
    h, n, n_kv, d, E, V, f = 32, 4, 2, 8, 8, 256, 16
    proj = 2 * h * (n * d + 2 * n_kv * d + n * d) + 2 * n * d * h  # q, k, v, the gate; o
    dense = 2 * h * V + 5 * proj + 2 * 3 * h * 64 + 4 * (2 * h * E + 2 * 3 * h * f)  # routers and shared experts
    assert flops_afmoe.dense_flops_per_token(cfg) == dense
    # position 20 of a window of 8: the full layer attends 21 positions, four sliding layers 8
    one = 2 * n * 2 * d * (21 + 4 * 8)
    assert flops_afmoe.attention_flops_span(cfg, 20, 21) == one
    by_hand = sum(2 * n * 2 * d * ((p + 1) + 4 * min(p + 1, 8)) for p in range(3, 30))
    assert flops_afmoe.attention_flops_span(cfg, 3, 30) == by_hand
    assert flops_afmoe.attention_flops_span(cfg, 0, 5) == sum(2 * n * 2 * d * 5 * (p + 1) for p in range(5))
    assert flops_afmoe.serve_flops_span(cfg, 3, 30) == 27 * dense + by_hand
    assert flops_afmoe.expert_flops(cfg, 10) == 10 * 2 * 3 * h * f
    assert flops_afmoe.expert_bytes(cfg, 3) == 3 * 3 * h * f * 2
    assert flops_afmoe.kv_read_bytes(cfg, 20) == {"global": 21 * 2 * n_kv * d * 2, "window": 4 * 8 * 2 * n_kv * d * 2}


def test_weights_are_the_seeds_and_in_the_type_asked(cell):
    a = weights_afmoe.flatten(weights_afmoe.make_weights(cell.config, 2**31 + 5))
    b = weights_afmoe.flatten(weights_afmoe.make_weights(cell.config, 2**31 + 5))
    c = weights_afmoe.flatten(weights_afmoe.make_weights(cell.config, 2**31 + 6))
    assert all(np.array_equal(a[p], b[p]) for p in a) and any(not np.array_equal(a[p], c[p]) for p in a)
    for path, leaf in a.items():
        name = path.rsplit("/", 1)[-1]
        assert leaf.dtype == (jnp.float32 if name in ("scale", "select_bias") else jnp.bfloat16), path
    # a layer made alone is the layer of the whole tree, and no two layers or experts are one draw
    key = weights_afmoe.seed_key(2**31 + 5)
    alone = weights_afmoe.flatten(weights_afmoe.make_layer(cell.config, key, 3), "layers_3")
    assert len(alone) == 15
    for path, leaf in alone.items():
        np.testing.assert_allclose(np.asarray(leaf, np.float32), np.asarray(a[path], np.float32), rtol=2e-7, err_msg=path)
    stack = np.asarray(a["layers_2/experts/gate_up"], np.float32)
    assert not np.array_equal(stack, np.asarray(a["layers_3/experts/gate_up"], np.float32))
    assert not np.array_equal(stack[0], stack[1]) and 0.015 < stack.std() < 0.025
    assert float(jnp.std(a["layers_1/experts/select_bias"])) > 0.03


def test_sound_run_is_correct(cell):
    res = run.run_cell(cell, seed=2**31 + 13, seconds=2.0, trace=False, device=CPU)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0, res["compared"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["compared"]["served_logit_gap"]["value"] < 1e-4


def _sample(seed):
    rs = np.random.RandomState(seed)
    return [{"prompt": rs.randint(0, 256, size=60).tolist(), "tokens": rs.randint(0, 256, size=40).tolist()} for _ in range(3)]


@pytest.mark.parametrize("fault", ["fp8", *reference.FAULTS])
def test_control_and_each_fault_are_not_correct(cell, fault):
    limits = cell.workload["limits"]
    kw = {"cast": "fp8"} if fault == "fp8" else {"faults": (fault,)}
    for seed in (3, 4, 5):
        got = serve_afmoe.served_gap(cell, seed, _sample(seed), **kw)
        # not correct by one of the limits
        assert got["gap"] > limits["served_logit_gap"] or got["mean_gap"] > limits["served_logit_gap_mean"], (fault, seed, got)


def _metric(name: str, obs: dict):
    with open(os.path.join(harness.BENCH_DIR, "metrics", f"{name}.json")) as f:
        spec = json.load(f)
    return harness.load_reader(spec["reader"])(obs, **spec.get("args", {}))


def test_the_cells_metric_files_tell_the_two_cache_kinds_launches_apart(monkeypatch):
    captured = [
        {"name": "decode_step", "attrs": {"kv_bytes_global": 100, "kv_bytes_window": 300, "active_slots": 4, "rows_past_window": 1}},
        {"name": "decode_step", "attrs": {"kv_bytes_global": 300, "kv_bytes_window": 500, "active_slots": 4, "rows_past_window": 3}},
        {"name": "round", "attrs": {}},
    ]
    monkeypatch.setattr(spans, "captured", lambda: captured)
    obs = {
        "peak": {"hbm_bytes_per_s": 1e9},
        "trace": {
            "ops": {"%paged_decode_attention.3": 8e-7, "%paged_decode_attention_window.1": 12e-7,
                    "%paged_decode_attention_window.2": 4e-7, "%fusion.9": 1.0},
            "programs": {"jit_decode_paged_fn": {"executions": 2}},
        },
    }
    # 200 bytes a decode at 1e9 B/s = 2e-7 s needed of 4e-7 s taken; 400 bytes = 4e-7 s of 8e-7 s
    assert _metric("global_decode_roofline.serve", obs) == pytest.approx(50.0)
    assert _metric("window_decode_roofline.serve", obs) == pytest.approx(50.0)
    assert _metric("ring_wrapped_rows_share.serve", obs) == pytest.approx(50.0)
    # a program without the window launches' name, or without the counter, gives nothing to read
    obs["trace"]["ops"] = {"%paged_decode_attention.3": 8e-7}
    assert _metric("window_decode_roofline.serve", obs) is None
    monkeypatch.setattr(spans, "captured", lambda: [{"name": "decode_step", "attrs": {"kv_bytes": 9, "active_slots": 4}}])
    assert _metric("ring_wrapped_rows_share.serve", obs) is None and _metric("global_decode_roofline.serve", obs) is None
