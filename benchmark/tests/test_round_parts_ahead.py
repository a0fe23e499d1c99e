"""The accepted round readers, unedited, over a capture of a round that sends
the next round's chunk ahead: a ``prefill_chunk`` inside ``decode_step``, and a
first token's ``pull`` under ``round``.  Each reads what its ``what`` says."""

import pytest

from benchmark import harness
from benchmark.readers import round_parts, spans
from benchmark.tests.test_round_parts import NAMES, at, capture, plain_round  # noqa: F401  (capture: a fixture)
from benchmark.tests.test_spans import _span


def ahead_round(i, t0, *, gap_ms, covered_ms, lands):
    """A round whose decode_step sends the next round's chunk behind the
    decode before its pull.  Without ``lands``: admit 0-2, prep 2-3, dispatch
    3-8, the chunk 8-13, pull 13-50.  With it, the chunk the last round sent
    ended a prompt: its first token is pulled 2-5, register 5-12, first token
    12-14, 1 ms nobody names, and the same decode_step 13 ms later; commit and
    metrics take 8 ms, the round ends 2 ms after them."""
    r, d, o = f"r{i}", f"d{i}", 13.0 if lands else 0.0
    landing = [
        _span("pull", f"q{i}", r, at(t0, 2), at(t0, 5), uid=7),
        _span("prefix_register", f"g{i}", r, at(t0, 5), at(t0, 12), prompt_tokens=1024, hashed_tokens=33280, created=64),
        _span("first_token", f"t{i}", r, at(t0, 12), at(t0, 14), uid=7),
    ]
    return [
        _span("admit", f"a{i}", r, at(t0, 0), at(t0, 2), admitted=0),
        *(landing if lands else []),
        _span("decode_prep", f"e{i}", r, at(t0, o + 2), at(t0, o + 3)),
        _span("decode_step", d, r, at(t0, o + 3), at(t0, o + 50)),
        _span("dispatch", f"x{i}", d, at(t0, o + 3), at(t0, o + 8)),
        _span("prefill_chunk", f"k{i}", d, at(t0, o + 8), at(t0, o + 13), real=64, ahead=1),
        _span("pull", f"p{i}", d, at(t0, o + 13), at(t0, o + 50)),
        _span("commit", f"c{i}", r, at(t0, o + 50), at(t0, o + 56), tokens=32),
        _span("round_metrics", f"m{i}", r, at(t0, o + 56), at(t0, o + 58)),
        _span(
            "round", r, None, at(t0, 0), at(t0, o + 60), round=i, decoding=32, prefilling=3, dispatches=2,
            host_gap_ms=gap_ms, covered_gap_ms=covered_ms, chunk_ahead=1,
        ),
    ]


def test_a_chunk_inside_decode_step_is_read_as_each_reader_says(capture):
    """The readers are the accepted ones, unedited: a ``prefill_chunk`` between
    ``dispatch`` and ``pull`` is one more enqueue inside the interval they
    already subtract, and a first token's ``pull`` under ``round`` opens the
    one gap the scheduler still counts."""
    # round 0 sends a chunk ahead: the pull at 50 has it queued behind, so the 10 ms to round 1's
    # dispatch (commit, metrics, 2 ms, admit) are covered and round 1 counts no host gap.  Round 1
    # sends the prompt's last chunk; round 2 pulls its first token at 2-5 and counts the 11 ms from
    # there to its dispatch at 16 (register 7, first token 2, prep 1: 1 ms has no name).
    # Round 3 is a plain one of a scheduler that says so: nothing was prefilling.
    plain = plain_round(3, 5.2, gap_ms=5.0)
    next(s for s in plain if s["name"] == "round")["attrs"].update(covered_gap_ms=0.0, chunk_ahead=0)
    rounds = [
        ahead_round(0, 5.0, gap_ms=3.0, covered_ms=0.0, lands=False),
        ahead_round(1, 5.06, gap_ms=0.0, covered_ms=10.0, lands=False),
        ahead_round(2, 5.12, gap_ms=11.0, covered_ms=10.0, lands=True),
        plain,
    ]
    for s in (s for r in rounds for s in r):
        capture.add_span(s)
    assert round_parts.mean_round_attr({}, attr="chunk_ahead") == pytest.approx(3 / 4)
    assert round_parts.mean_round_attr({}, attr="covered_gap_ms") == pytest.approx(20.0 / 4)
    assert round_parts.mean_round_attr({}, attr="host_gap_ms") == pytest.approx((3.0 + 0.0 + 11.0 + 5.0) / 4)
    # what the named spans leave of each round's host gap: the lead of rounds 0 and 3 is admit and
    # prep, round 1 has no gap to name, round 2's one gap runs from the landing pull's end to the dispatch
    assert round_parts.host_gap_unspanned_ms({}, names=NAMES) == pytest.approx((0.0 + 0.0 + 1.0 + 0.0) / 4)
    # the round less first enqueue's start to last pull's end: 60 - (50 - 3) for rounds 0 and 1;
    # round 2 is 73 long and its first enqueue is the dispatch at 16, so the landing (pull, register,
    # first token) is on the host's side of it: 73 - (63 - 16); the plain round 60 - (50 - 5)
    assert spans.round_host_ms({}) == pytest.approx((13.0 + 13.0 + 26.0 + 15.0) / 4)
    assert spans.pull_wait_ms({}) == pytest.approx((37.0 + 37.0 + 3.0 + 37.0 + 44.0) / 4)
    assert round_parts.span_ms_per_round({}, names=["prefix_lookup", "prefix_register"]) == pytest.approx(7.0 / 4)
    # a program from before this counter carries no ``chunk_ahead``: nothing is read, nothing raises
    capture.clear()
    for s in plain_round(0, 5.0, gap_ms=5.0):
        capture.add_span(s)
    assert round_parts.mean_round_attr({}, attr="chunk_ahead") is None
    spec = harness.load_json("metrics", "chunk_ahead_share.serve.json")
    assert spec["reader"] == "round_parts.mean_round_attr" and spec["args"] == {"attr": "chunk_ahead"}
    entry = next(m for m in harness.load_benchmark()["per_layer"] if m["name"] == "chunk_ahead_share.serve")
    serving = [w["name"] for w in harness.load_benchmark()["workloads"] if w["name"].startswith("serve.")]
    assert (entry["layer"], entry["source"], entry["better"], entry["unit"], entry["moves"], entry["workloads"]) == (
        "scheduler", "program_counter", "higher", "share", "serve_tokens_per_s", serving,
    )
