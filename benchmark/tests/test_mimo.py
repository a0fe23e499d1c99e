"""The ``mimo_v2`` family's benchmark files at a size a CPU holds: required
operations against hand counts, weights from the seed, ``correct`` true for
the program and false for the control and for each fault a forward can have,
and the readers this family's metrics brought."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_mimo, harness, run, weights_mimo
from benchmark.drivers import serve_mimo
from benchmark.readers import inside, span_ratio, spans, trace
from benchmark.reference import mimo as reference

from .conftest import CPU, DATA


@pytest.fixture(scope="module")
def cell():
    with open(os.path.join(DATA, "bench_tiny_mimo.json")) as f:
        return harness.Cell("serve.tiny_mimo", json.load(f), base=DATA)


def test_flops_against_hand_counts(cell):
    cfg = cell.config
    h, n, dk, dv, E, V = 32, 4, 12, 8, 8, 256
    proj = lambda n_kv: 2 * h * (n * dk + n_kv * dk + n_kv * dv) + 2 * n * dv * h
    dense = 2 * h * V + 2 * proj(1) + 5 * proj(2) + 2 * 3 * h * 64 + 6 * 2 * h * E
    assert flops_mimo.dense_flops_per_token(cfg) == dense
    # position 20 of a window of 8: two global layers attend 21 positions, five window layers 8
    one = 2 * n * (dk + dv) * (2 * 21 + 5 * 8)
    assert flops_mimo.attention_flops_span(cfg, 20, 21) == one
    by_hand = sum(2 * n * (dk + dv) * (2 * (p + 1) + 5 * min(p + 1, 8)) for p in range(3, 30))
    assert flops_mimo.attention_flops_span(cfg, 3, 30) == by_hand
    assert flops_mimo.serve_flops_span(cfg, 3, 30) == 27 * dense + by_hand
    assert flops_mimo.expert_flops(cfg, 10) == 10 * 2 * 3 * h * 16
    assert flops_mimo.expert_bytes(cfg, 3) == 3 * 3 * h * 16 * 2


def test_weights_are_the_seeds_and_in_the_type_asked(cell):
    a = weights_mimo.flatten(weights_mimo.make_weights(cell.config, 2**31 + 5))
    b = weights_mimo.flatten(weights_mimo.make_weights(cell.config, 2**31 + 5))
    c = weights_mimo.flatten(weights_mimo.make_weights(cell.config, 2**31 + 6))
    assert all(np.array_equal(a[p], b[p]) for p in a) and any(not np.array_equal(a[p], c[p]) for p in a)
    for path, leaf in a.items():
        name = path.rsplit("/", 1)[-1]
        assert leaf.dtype == (jnp.float32 if name in ("scale", "sink", "select_bias") else jnp.bfloat16), path
    # a layer made alone is the layer of the whole tree
    key = weights_mimo.seed_key(2**31 + 5)
    alone = weights_mimo.flatten(weights_mimo.make_layer(cell.config, key, 3), "layers_3")
    assert len(alone) == 9
    for path, leaf in alone.items():
        # 1 + 0.02 * draw may or may not be fused into one rounding
        np.testing.assert_allclose(np.asarray(leaf, np.float32), np.asarray(a[path], np.float32), rtol=2e-7, err_msg=path)
    assert float(jnp.std(a["layers_1/attn/sink"])) > 0.3 and float(jnp.std(a["layers_1/experts/select_bias"])) > 0.03


def test_sound_run_is_correct(cell):
    res = run.run_cell(cell, seed=2**31 + 13, seconds=2.0, trace=False, device=CPU)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0, res["compared"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert res["compared"]["served_logit_gap"]["value"] < 1e-4


def _sample(seed):
    rs = np.random.RandomState(seed)
    return [{"prompt": rs.randint(0, 256, size=60).tolist(), "tokens": rs.randint(0, 256, size=40).tolist()} for _ in range(3)]


@pytest.mark.parametrize("fault", ["fp8", "no_sink", "no_select_bias", "no_value_scale", "window_minus_one"])
def test_control_and_each_fault_are_not_correct(cell, fault):
    limit = cell.workload["limits"]["served_logit_gap"]
    kw = {"cast": "fp8"} if fault == "fp8" else {"faults": (fault,)}
    for seed in (3, 4, 5):
        got = serve_mimo.served_gap(cell, seed, _sample(seed), **kw)
        # not correct by one of the limits (the widest gap here: a single flipped token barely moves the mean)
        assert got["gap"] > limit or got["mean_gap"] > cell.workload["limits"]["served_logit_gap_mean"], (fault, seed, got)


def test_ops_inside_counts_only_one_programs_executions():
    planes = {"/device:TPU:0": {
        trace.MODULES_LINE: [("jit_decode_paged_fn(1)", 0.0, 100.0), ("jit_prefill_chunk_fn(2)", 100.0, 300.0),
                             ("jit_decode_paged_fn(1)", 400.0, 100.0)],
        trace.OPS_LINE: [("%ragged-dot-none.1", 10.0, 20.0), ("%fusion.3", 40.0, 10.0), ("%ragged-dot-none.1", 150.0, 200.0),
                         ("%ragged-dot-none.2", 410.0, 30.0)],
    }}
    got = inside.ops_inside(planes, "jit_decode_paged_fn")
    assert got["executions"] == 2
    assert got["ops"] == {"%ragged-dot-none.1": 20e-9, "%fusion.3": 10e-9, "%ragged-dot-none.2": 30e-9}
    obs = {"inside": {"jit_decode_paged_fn": got}, "peak": {"hbm_bytes_per_s": 1e9}}
    assert inside.pattern_ms(obs, "jit_decode_paged_fn", "^%ragged-dot") == pytest.approx(1e3 * 25e-9)
    assert inside.pattern_ms(obs, "jit_decode_paged_fn", "^%nothing") is None
    assert inside.pattern_ms({}, "jit_decode_paged_fn", "^%ragged-dot") is None


def test_span_readers_read_the_capture(monkeypatch):
    captured = [
        {"name": "decode_step", "attrs": {"kv_bytes": 100, "kv_bytes_window": 25, "expert_bytes": 50}},
        {"name": "decode_step", "attrs": {"kv_bytes": 200, "kv_bytes_window": 150, "expert_bytes": 150}},
        {"name": "decode_step", "attrs": {"kv_bytes": 300}},
        {"name": "round", "attrs": {}},
    ]
    monkeypatch.setattr(spans, "captured", lambda: captured)
    assert span_ratio.mean_share_pct({}, "decode_step", "kv_bytes_window", "kv_bytes") == pytest.approx(50.0)
    obs = {"inside": {"p": {"ops": {"%ragged-dot-none": 4e-7}, "executions": 2}}, "peak": {"hbm_bytes_per_s": 1e9}}
    # 100 bytes a decode at 1e9 B/s = 1e-7 s needed; 2e-7 s taken an execution
    assert inside.bytes_roofline_pct(obs, "decode_step", "expert_bytes", "^%ragged-dot", "p") == pytest.approx(50.0)
    monkeypatch.setattr(spans, "captured", lambda: [])
    assert span_ratio.mean_share_pct({}, "decode_step", "kv_bytes_window", "kv_bytes") is None
    assert inside.bytes_roofline_pct(obs, "decode_step", "expert_bytes", "^%ragged-dot", "p") is None
