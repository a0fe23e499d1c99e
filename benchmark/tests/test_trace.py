"""The trace reduction on a small trace recorded on the chip
(``data/trace_events.json``: one ``train_step`` of ``train.pythia_1b.relora_r128``
with the operations inside it, my chip run, PR 25) and on hand-made events."""

import json
import os

import pytest

from benchmark.readers import trace

from .conftest import DATA


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_events.json")) as f:
        raw = json.load(f)
    return {p: {line: [tuple(e) for e in events] for line, events in lines.items()} for p, lines in raw.items()}


def test_busy_union_of_recorded_trace(recorded):
    s = trace.reduce(recorded, window_s=0.4712)
    # the operations nest (a while holds its body): the union counts each instant once
    ops = recorded["/device:TPU:0"][trace.OPS_LINE]
    assert sum(d for _, _, d in ops) / 1e9 > 1.5 * s["busy_s"]
    assert s["busy_s"] == pytest.approx(0.468190146, rel=1e-9)
    assert s["idle_share"] == pytest.approx(1 - 0.468190146 / 0.4712, rel=1e-9)


def test_time_per_program_of_recorded_trace(recorded):
    s = trace.reduce(recorded, window_s=0.4712)
    step = s["programs"]["jit_train_step"]
    assert step["executions"] == 1 and step["device_s"] == pytest.approx(0.468208843, rel=1e-9)
    obs = {"trace": s}
    assert trace.program_ms(obs, "jit_train_step") == pytest.approx(468.208843, rel=1e-9)
    assert trace.program_ms(obs, "jit_decode_paged_fn") is None


def test_pattern_sum_of_recorded_trace(recorded):
    s = trace.reduce(recorded, window_s=0.4712)
    obs = {"trace": s, "peak": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "work": {"flash_attention": {"flops": 3.85e12, "bytes": 3.2e9}}}
    # 16 layers x (2 forward + dq + dkv) flash kernels
    hits = [k for k in s["ops"] if k.startswith("%flash_")]
    assert len(hits) == 4
    assert trace.pattern_seconds(obs, "^%flash_(attention|mha_bwd)") == pytest.approx(0.059579576, rel=1e-6)
    assert trace.kernel_roofline_pct(obs, "^%flash_(attention|mha_bwd)", "flash_attention") == pytest.approx(
        100 * (3.85e12 / 197e12) / 0.059579576, rel=1e-6
    )
    assert trace.pattern_seconds(obs, "^%no_such_kernel") is None
    assert trace.kernel_roofline_pct(obs, "^%no_such_kernel", "flash_attention") is None


def test_breakdown_counts_self_time(recorded):
    s = trace.reduce(recorded, window_s=0.4712)
    b = trace.breakdown(s)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    names = [n for n, _ in b["device_ops"]]
    # the layer loops hold nearly all the step, but none of it is their own
    assert not any(n.startswith("%while") for n in names)
    assert sum(s["self_ops"].values()) == pytest.approx(s["busy_s"], rel=1e-3)


def test_union_gaps_and_self_time_on_hand_made_events():
    events = [("a", 0.0, 10.0), ("b", 2.0, 3.0), ("c", 20.0, 5.0), ("d", 24.0, 6.0)]
    assert trace._union_ns(events) == 20.0
    assert trace._gaps(events) == [(10.0, 10.0, "a", "c")]
    assert trace._self_ns(events) == {"a": 7.0, "b": 3.0, "c": 4.0, "d": 6.0}
    planes = {"/device:TPU:0": {trace.OPS_LINE: events, trace.MODULES_LINE: [("jit_f(12)", 0.0, 10.0), ("jit_f(12)", 20.0, 10.0)]}}
    s = trace.reduce(planes, window_s=40e-9)
    assert s["idle_share"] == pytest.approx(0.5)
    assert s["programs"] == {"jit_f": {"device_s": pytest.approx(20e-9), "executions": 2}}


def test_no_device_plane_gives_nothing_to_read():
    assert trace.reduce({}, window_s=1.0) == {}
    obs = {"trace": {}}
    assert trace.idle_share_pct(obs) is None and trace.program_ms(obs, "jit_train_step") is None


def test_short_name_keeps_instruction_and_custom_call_target():
    assert trace.short_name("%fusion.12 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop") == "%fusion.12"
    assert (
        trace.short_name('%attention.9 = bf16[32,16,128]{2,1,0} custom-call(s32[32,128] %a), custom_call_target="tpu_custom_call"')
        == "%attention.9 [tpu_custom_call]"
    )
    assert trace.short_name("jit_train_step(123)") == "jit_train_step(123)"
