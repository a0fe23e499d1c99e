"""The host-gap readers' arithmetic on a hand-written capture, and that they
read nothing on a capture without the counter, or a truncated one."""

import pytest

from benchmark import harness
from benchmark.readers import round_parts
from benchmark.tests.test_spans import _span

NAMES = harness.load_json("metrics", "host_gap_unspanned_ms.serve.json")["args"]["names"]


def at(t0, ms):
    return t0 + ms / 1e3


def plain_round(i, t0, *, gap_ms):
    """A round of 60 ms whose only gap is its lead: admit 0-4, prep 4-5,
    dispatch at 5, pull to 50, commit 50-56, metrics 56-58; after it the
    loop's flush 60-61 and claim 62-64."""
    r, d = f"r{i}", f"d{i}"
    return [
        _span("admit", f"a{i}", r, at(t0, 0), at(t0, 4), admitted=0),
        _span("decode_prep", f"e{i}", r, at(t0, 4), at(t0, 5)),
        _span("decode_step", d, r, at(t0, 5), at(t0, 50)),
        _span("dispatch", f"x{i}", d, at(t0, 5), at(t0, 6)),
        _span("pull", f"p{i}", d, at(t0, 6), at(t0, 50)),
        _span("commit", f"c{i}", r, at(t0, 50), at(t0, 56), tokens=32),
        _span("round_metrics", f"m{i}", r, at(t0, 56), at(t0, 58)),
        _span("round", r, None, at(t0, 0), at(t0, 60), round=i, decoding=32, prefilling=i, dispatches=1, host_gap_ms=gap_ms),
        _span("flush_outbox", f"f{i}", None, at(t0, 60), at(t0, 61)),
        _span("claim", f"l{i}", None, at(t0, 62), at(t0, 64), claimed=0),
    ]


def first_token_round(i, t0, *, gap_ms):
    """A round whose chunk ends a 1,024-token prompt: admit 0-10 with the
    lookup 1-9 inside, chunk 10-20 pulled at 20, register 20-28, first token
    28-30, 1 ms nobody names, prep 31-32, dispatch at 32, pull to 70."""
    r, d, k, a = f"r{i}", f"d{i}", f"k{i}", f"a{i}"
    return [
        _span("admit", a, r, at(t0, 0), at(t0, 10), admitted=1),
        _span("prefix_lookup", f"u{i}", a, at(t0, 1), at(t0, 9), prompt_tokens=1024, hashed_tokens=32256, hit_tokens=0),
        _span("prefill_chunk", k, r, at(t0, 10), at(t0, 20), real=64),
        _span("pull", f"q{i}", k, at(t0, 18), at(t0, 20)),
        _span("prefix_register", f"g{i}", r, at(t0, 20), at(t0, 28), prompt_tokens=1024, hashed_tokens=33280, created=64),
        _span("first_token", f"t{i}", r, at(t0, 28), at(t0, 30), uid=7),
        _span("decode_prep", f"e{i}", r, at(t0, 31), at(t0, 32)),
        _span("decode_step", d, r, at(t0, 32), at(t0, 70)),
        _span("dispatch", f"x{i}", d, at(t0, 32), at(t0, 33)),
        _span("pull", f"p{i}", d, at(t0, 33), at(t0, 70)),
        _span("commit", f"c{i}", r, at(t0, 70), at(t0, 76), tokens=33),
        _span("round_metrics", f"m{i}", r, at(t0, 76), at(t0, 78)),
        _span("round", r, None, at(t0, 0), at(t0, 80), round=i, decoding=32, prefilling=3, dispatches=2, host_gap_ms=gap_ms),
    ]


@pytest.fixture
def capture(monkeypatch):
    from relora_tpu.obs import flight

    rec = flight.FlightRecorder()
    monkeypatch.setattr(flight, "default_recorder", lambda: rec)
    return rec


def test_the_host_gap_readers_on_a_hand_written_capture(capture):
    # round 0 starts the session: its lead (5 ms, all of it admit and prep) lies inside the capture.
    # round 1 follows 65 ms on: its lead reaches back to round 0's pull at 50, 15 ms of round 0's commit
    # and metrics, the loop's flush and claim and 4 ms with no span, then its own admit 0-10; in it, the
    # chunk's pull at 20 opens 12 ms to the dispatch at 32, of which 1 ms has no name.
    spans = plain_round(0, 5.0, gap_ms=5.0) + first_token_round(1, 5.065, gap_ms=25.0 + 12.0)
    spans.append(_span("round", "idle", None, 6.0, 6.1, round=2, dispatches=0, host_gap_ms=0.0))  # dispatched nothing
    for s in spans:
        capture.add_span(s)
    assert round_parts.mean_round_attr({}, attr="host_gap_ms") == pytest.approx((5.0 + 37.0) / 2)
    assert round_parts.mean_round_attr({}, attr="prefilling") == pytest.approx((0 + 3) / 2)
    assert round_parts.span_ms_per_round({}, names=["admit"]) == pytest.approx((4.0 + 10.0) / 2)
    assert round_parts.span_ms_per_round({}, names=["commit"]) == pytest.approx(6.0)
    assert round_parts.span_ms_per_round({}, names=["prefix_lookup", "prefix_register"]) == pytest.approx(16.0 / 2)
    ratio = round_parts.attr_ratio({}, names=["prefix_lookup", "prefix_register"], numerator="hashed_tokens", denominator="prompt_tokens")
    assert ratio == pytest.approx((32256 + 33280) / 2048)
    # round 0 leaves nothing unnamed; round 1 leaves 58-60 and 61-62 and 64-65 of the lead, and 30-31 inside
    assert round_parts.host_gap_unspanned_ms({}, names=NAMES) == pytest.approx((0.0 + 5.0) / 2)
    # with the spans of before this counter (admit, commit, round_metrics) most of the gap has no name
    assert round_parts.host_gap_unspanned_ms({}, names=["admit", "commit", "round_metrics"]) == pytest.approx((1.0 + 19.0) / 2)


def test_a_dropped_lead_and_one_before_the_session_are_not_laid_over_spans(capture):
    # the scheduler dropped round 1's lead (an idle wait): only the 12 ms inside it are counted
    for s in plain_round(0, 5.0, gap_ms=5.0) + first_token_round(1, 5.5, gap_ms=12.0):
        capture.add_span(s)
    assert round_parts.host_gap_unspanned_ms({}, names=NAMES) == pytest.approx((0.0 + 1.0) / 2)
    # a lead that reaches back before the capture's first span: the round is left out of this mean
    capture.clear()
    for s in plain_round(0, 5.0, gap_ms=9.0) + plain_round(1, 5.065, gap_ms=15.0):
        capture.add_span(s)
    assert round_parts.host_gap_unspanned_ms({}, names=NAMES) == pytest.approx(15.0 - 11.0)
    assert round_parts.mean_round_attr({}, attr="host_gap_ms") == pytest.approx(12.0)


def test_no_counter_no_span_or_a_truncated_capture_reads_nothing(capture):
    readings = lambda: [  # noqa: E731
        round_parts.mean_round_attr({}, attr="host_gap_ms"),
        round_parts.mean_round_attr({}, attr="prefilling"),
        round_parts.span_ms_per_round({}, names=["admit"]),
        round_parts.span_ms_per_round({}, names=["prefix_lookup", "prefix_register"]),
        round_parts.attr_ratio({}, names=["prefix_lookup", "prefix_register"], numerator="hashed_tokens", denominator="prompt_tokens"),
        round_parts.host_gap_unspanned_ms({}, names=NAMES),
    ]
    assert readings() == [None] * 6  # nothing captured
    # a program from before the counter: rounds with ``prefilling`` and ``admit``, and no ``host_gap_ms``
    for s in plain_round(0, 5.0, gap_ms=5.0):
        s["attrs"].pop("host_gap_ms", None)
        capture.add_span(s)
    assert readings() == [None] * 6
    capture.clear()
    for s in plain_round(0, 5.0, gap_ms=5.0):
        capture.add_span(s)
    got = readings()
    assert got[0] == 5.0 and got[1] == 0 and got[2] == pytest.approx(4.0) and got[5] == pytest.approx(0.0, abs=1e-9)
    assert got[3] is None and got[4] is None  # a cell that runs without the prefix cache
    capture.dropped_profiled = 3  # the session outgrew the capture's bound
    assert readings() == [None] * 6


def test_every_new_metric_names_a_reader_and_the_cells_that_report_it():
    bench = harness.load_benchmark()
    serving = [w["name"] for w in bench["workloads"] if w["name"].startswith("serve.")]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    new = {name: by_name[name] for name in (
        "host_gap_ms.serve", "host_gap_unspanned_ms.serve", "admit_ms.serve", "commit_ms.serve",
        "prefix_hash_ms.serve", "prefix_hash_amplification.serve", "prefill_backlog_slots.serve",
    )}
    for name, m in new.items():
        spec = harness.load_json("metrics", f"{name}.json")
        assert callable(harness.load_reader(spec["reader"]))
        assert (m["layer"], m["source"], m["better"]) == ("scheduler", "program_counter", "lower")
        everywhere = name in ("host_gap_ms.serve", "host_gap_unspanned_ms.serve", "admit_ms.serve", "commit_ms.serve")
        assert m["workloads"] == (serving if everywhere else serving[:1])
