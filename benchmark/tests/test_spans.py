"""The span readers' arithmetic on a hand-written capture, and that they
read nothing (never 0) where the program captured nothing."""

import pytest

from benchmark import harness
from benchmark.readers import spans


def _span(name, span_id, parent_id, t_start, t_end, **attrs):
    return {"name": name, "span_id": span_id, "parent_id": parent_id, "t_start": t_start, "t_end": t_end,
            "dur_s": t_end - t_start, "attrs": attrs, "profiled": True}


def _round(i, t0, *, chunk):
    """One round of 100 ms (110 with a chunk): admit 0-10, chunk 10-20,
    dispatch 20-25 (10-15 without), pull to 70, commit, metrics."""
    r, d = f"r{i}", f"d{i}"
    at = lambda ms: t0 + ms / 1e3  # noqa: E731
    shift = 10 if chunk else 0
    out = [
        _span("admit", f"a{i}", r, at(0), at(10), admitted=0),
        _span("decode_step", d, r, at(10 + shift), at(70 + shift), kv_bytes=8.19e8 * (i + 1)),
        _span("dispatch", f"x{i}", d, at(10 + shift), at(15 + shift)),
        _span("pull", f"p{i}", d, at(15 + shift), at(70 + shift)),
        _span("commit", f"c{i}", r, at(70 + shift), at(90 + shift), tokens=32),
        _span("round_metrics", f"m{i}", r, at(90 + shift), at(100 + shift)),
        _span("round", r, None, at(0), at(100 + shift), round=i, decoding=32, prefilling=1, dispatches=2 + chunk),
    ]
    if chunk:
        out[1:1] = [_span("prefill_chunk", f"k{i}", r, at(10), at(20), real=64), _span("pull", f"q{i}", f"k{i}", at(18), at(20))]
    return out


@pytest.fixture
def capture(monkeypatch):
    held: list = []
    monkeypatch.setattr(spans, "captured", lambda: list(held))
    return held


def test_round_readers_on_a_hand_written_capture(capture):
    capture += _round(0, 5.0, chunk=False) + _round(1, 6.0, chunk=True)
    capture.append(_span("round", "idle", None, 7.0, 7.5, round=2, dispatches=0))  # dispatched nothing: not a round
    capture.append(_span("pull", "stray", "gone", 8.0, 9.0))  # its round was cut by the session's edge
    # round 0: 100 - (70 - 10) = 40; round 1: 110 - (80 - 10) = 40 (the chunk's start opens the interval)
    assert spans.round_host_ms({}) == pytest.approx(40.0)
    # round 0 waits 55 ms in its one pull; round 1 waits 55 + 2
    assert spans.pull_wait_ms({}) == pytest.approx((55.0 + 57.0) / 2)
    assert spans.mean_ms({}, name="commit") == pytest.approx(20.0)
    assert spans.mean_ms({}, name="data_fetch") is None


def test_bytes_roofline_is_a_ratio_of_per_dispatch_means(capture):
    capture += _round(0, 5.0, chunk=False) + _round(1, 6.0, chunk=True)
    peak = harness.peaks_for("TPU v5 lite")
    args = dict(span="decode_step", attr="kv_bytes", pattern="^%paged_decode_attention", program="jit_decode_paged_fn")
    trace = {
        "ops": {"%paged_decode_attention.9 [tpu_custom_call]": 0.15, "%fusion.1": 9.0, "%attention.9 [tpu_custom_call]": 5.0},
        "programs": {"jit_decode_paged_fn": {"device_s": 0.4, "executions": 3.0}},
    }
    # mean kv_bytes 1.5 x 8.19e8 -> 1.5 ms at 819 GB/s; the kernel takes 50 ms a dispatch
    assert spans.bytes_roofline_pct({"trace": trace, "peak": peak}, **args) == pytest.approx(100 * 1.5e-3 / 0.05)
    assert spans.bytes_roofline_pct({"trace": {}, "peak": peak}, **args) is None
    unnamed = {"ops": {"%attention.9 [tpu_custom_call]": 5.0}, "programs": trace["programs"]}
    assert spans.bytes_roofline_pct({"trace": unnamed, "peak": peak}, **args) is None


def test_an_empty_capture_reads_nothing(capture):
    trace = {"ops": {"%paged_decode_attention.9": 0.1}, "programs": {"jit_decode_paged_fn": {"device_s": 0.4, "executions": 3.0}}}
    obs = {"trace": trace, "peak": harness.peaks_for("TPU v5 lite")}
    assert spans.round_host_ms(obs) is None and spans.pull_wait_ms(obs) is None
    assert spans.mean_ms(obs, name="data_fetch") is None
    assert spans.bytes_roofline_pct(obs, span="decode_step", attr="kv_bytes", pattern="paged", program="jit_decode_paged_fn") is None


def test_captured_reads_the_programs_recorder_and_survives_its_absence(monkeypatch):
    from relora_tpu.obs import flight

    rec = flight.FlightRecorder()
    monkeypatch.setattr(flight, "default_recorder", lambda: rec)
    assert spans.captured() == []
    rec.add_span(_span("data_fetch", "s1", None, 1.0, 1.002))
    assert [s["name"] for s in spans.captured()] == ["data_fetch"]
    assert spans.mean_ms({}, name="data_fetch") == pytest.approx(2.0)
    monkeypatch.setattr(flight, "default_recorder", lambda: object())  # a program from before the capture
    assert spans.captured() == [] and spans.round_host_ms({}) is None
