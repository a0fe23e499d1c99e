"""The host's part of a serving round, counted where it happens
(docs/observability.md, "The host gap"): the scheduler's own count of the
time between a blocking pull's return and the next enqueue, the spans that
name every part of it, and what the tracing no longer records."""

import asyncio
import re
import threading
import time

import jax
import pytest

from relora_tpu.obs.flight import FlightRecorder
from relora_tpu.obs.metrics import MetricsRegistry
from relora_tpu.obs.tracer import Tracer
from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler, Request
from relora_tpu.serve.server import GenerateServer
from tests.test_paging import TINY_LLAMA, make_engines
from tests.test_server import _http, _sse_events

pytestmark = pytest.mark.serve

MS = 1e-3


class Clock:
    """The tracer's clock, moved by the test alone."""

    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def advance(self, seconds: float) -> None:
        self.t += seconds


def costing(clock: Clock, seconds: float, fn):
    """``fn``, taking ``seconds`` of the injected clock."""

    def timed(*args, **kwargs):
        clock.advance(seconds)
        return fn(*args, **kwargs)

    return timed


@pytest.fixture(scope="module")
def engine():
    return make_engines(TINY_LLAMA)[1]  # page 8, chunk 8, cache 32


@pytest.fixture
def drive(engine):
    """A paged scheduler with the prefix cache on, under a tracer whose clock
    only the test moves: the device's work and each piece of host work take
    the times given here, nothing else takes any."""
    clock, rec, registry = Clock(), FlightRecorder(), MetricsRegistry()
    sched = PagedContinuousBatchingScheduler(
        engine, max_batch=2, eos_id=-1, key=jax.random.PRNGKey(0),
        tracer=Tracer(service="serve", recorder=rec, clock=clock), obs_registry=registry,
    )
    for name, seconds in (("prefill_chunk", 40 * MS), ("decode_paged", 30 * MS)):
        setattr(engine, name, costing(clock, seconds, getattr(engine, name)))  # the device's: between enqueue and pull
    cache = sched.prefix_cache
    cache.lookup = costing(clock, 3 * MS, cache.lookup)
    cache.register = costing(clock, 7 * MS, cache.register)
    sched._round_metrics = costing(clock, 1 * MS, sched._round_metrics)
    on_token = costing(clock, 2 * MS, lambda uid, token, index: None)
    try:
        yield sched, clock, rec, registry, on_token
    finally:
        del engine.prefill_chunk, engine.decode_paged  # the instance attributes; the methods are the class's


def round_spans(rec):
    spans = rec.spans()
    by_id = {s["span_id"]: s for s in spans}
    rounds = [s for s in spans if s["name"] == "round"]
    children = {r["span_id"]: [s["name"] for s in spans if s["parent_id"] == r["span_id"]] for r in rounds}
    return spans, by_id, rounds, children


def test_host_gap_is_the_stamped_intervals_and_every_part_has_a_span(drive):
    sched, clock, rec, registry, on_token = drive
    sched.submit(Request(uid=1, prompt=list(range(1, 13)), max_new_tokens=3), on_token=on_token)
    sched.step()  # round 0: admission and the prompt's first chunk; nothing has been pulled yet
    sched.step()  # round 1: the last chunk, the first token, the first decode
    clock.advance(4 * MS)  # the server's loop between two rounds
    sched.step()  # round 2: the second decode; the request is done
    assert not sched.has_work()
    sched.drop_host_gap()  # as the server's loop does before it waits for a request
    clock.advance(500 * MS)
    sched.submit(Request(uid=2, prompt=[5, 6, 7, 8, 9], max_new_tokens=2), on_token=on_token)
    sched.step()  # round 3: one chunk ends the prompt, first token, one decode

    spans, by_id, rounds, children = round_spans(rec)
    gaps = [r["attrs"]["host_gap_ms"] for r in rounds]
    # round 1: from the first token's pull to the decode's dispatch, register 7 + the token's callback 2
    # round 2: round 1's commit 2 and metrics 1, the loop's 4, carried over the round span's end
    # round 3: 7 + 2 again; the 500 ms wait and the lookup before the first enqueue are not counted
    assert gaps == pytest.approx([0.0, 9.0, 7.0, 9.0], abs=1e-9)
    hist = registry.histogram("host_gap_seconds")
    assert hist.count == 4 and hist.total == pytest.approx(0.025, abs=1e-12)

    assert children[rounds[0]["span_id"]] == ["admit", "prefill_chunk"]
    assert children[rounds[1]["span_id"]] == [
        "admit", "prefill_chunk", "prefix_register", "first_token", "decode_prep", "decode_step", "commit", "round_metrics",
    ]
    assert children[rounds[2]["span_id"]] == ["admit", "decode_prep", "decode_step", "commit", "round_metrics"]
    lookups = [s for s in spans if s["name"] == "prefix_lookup"]
    assert [by_id[s["parent_id"]]["name"] for s in lookups] == ["admit", "admit"]
    assert [s["attrs"] for s in lookups] == [
        {"prompt_tokens": 12, "hashed_tokens": 8, "hit_tokens": 0},  # one whole page below the last token
        {"prompt_tokens": 5, "hashed_tokens": 0, "hit_tokens": 0},
    ]
    registers = [s["attrs"] for s in spans if s["name"] == "prefix_register"]
    assert registers == [
        {"prompt_tokens": 12, "hashed_tokens": 8, "created": 1},
        {"prompt_tokens": 5, "hashed_tokens": 0, "created": 0},
    ]
    # the first token's stretch is inside the interval round_host_ms subtracts: it lies
    # between the chunk's start and the decode's pull, and is host gap all the same
    first = next(s for s in spans if s["name"] == "first_token")
    assert first["dur_s"] == pytest.approx(2 * MS, abs=1e-12)
    prep = [s for s in spans if s["name"] == "decode_prep"]
    steps = [s for s in spans if s["name"] == "decode_step"]
    assert len(prep) == len(steps) == 3 and all("kv_bytes" in s["attrs"] for s in steps)


def test_a_step_that_dispatches_nothing_drops_the_gap(drive):
    sched, clock, rec, registry, on_token = drive
    sched.submit(Request(uid=1, prompt=[1, 2, 3], max_new_tokens=2), on_token=on_token)
    sched.step()
    assert sched._pull_stamp is not None and not sched.has_work()
    clock.advance(50 * MS)
    sched.step()  # nothing to run: no round, and what it waited is nobody's
    assert sched._pull_stamp is None
    sched.submit(Request(uid=2, prompt=[4, 5, 6], max_new_tokens=2), on_token=on_token)
    sched.step()
    _, _, rounds, _ = round_spans(rec)
    assert [r["attrs"]["host_gap_ms"] for r in rounds] == pytest.approx([9.0, 9.0], abs=1e-9)


# -- the chunk sent ahead (docs/observability.md, "The chunk goes ahead") --------


def below(spans, span, name=None):
    """The names (or, with ``name``, the spans so called) of ``span``'s children."""
    kids = [s for s in spans if s["parent_id"] == span["span_id"]]
    return [s for s in kids if s["name"] == name] if name else [s["name"] for s in kids]


def test_the_next_rounds_chunk_goes_behind_the_decode_before_its_pull(drive):
    sched, clock, rec, registry, on_token = drive
    sched.submit(Request(uid=1, prompt=[5, 6, 7, 8, 9], max_new_tokens=6), on_token=on_token)
    sched.step()  # round 0: uid 1's chunk, first token and first decode; nothing is prefilling behind it
    assert sched._ahead is None
    sched.submit(Request(uid=2, prompt=list(range(1, 21)), max_new_tokens=4), on_token=on_token)
    sched.step()  # round 1: uid 2's chunk 1 (none was sent for it), the decode, chunk 2 behind it, the pull
    assert sched._ahead.slot.request.uid == 2 and sched._ahead.first is None and sched._pull_covered
    clock.advance(4 * MS)  # the server's loop
    sched.step()  # round 2: chunk 2 is its chunk: the decode, chunk 3 (which ends the prompt) behind it
    assert sched._ahead.first is not None and sched._slots[1].prefill_progress == 20 and not sched._slots[1].decoding
    sched.step()  # round 3: the first token is read just before the decode uid 2 rides
    assert sched._ahead is None and sched._slots[1].decoding and len(sched._slots[1].tokens) == 2

    spans, by_id, rounds, children = round_spans(rec)
    names = [children[r["span_id"]] for r in rounds]
    tail = ["decode_prep", "decode_step", "commit", "round_metrics"]
    assert names[1] == ["admit", "prefill_chunk", *tail]
    assert names[2] == ["admit", *tail]  # no second chunk: the one sent ahead was this round's
    assert names[3] == ["admit", "pull", "prefix_register", "first_token", *tail]
    steps = [below(spans, r, "decode_step")[0] for r in rounds]
    assert [below(spans, d) for d in steps] == [
        ["dispatch", "pull"], ["dispatch", "prefill_chunk", "pull"], ["dispatch", "prefill_chunk", "pull"], ["dispatch", "pull"],
    ]
    sent = [c["attrs"] for d in steps for c in below(spans, d, "prefill_chunk")]
    assert [(c["uid"], c["start"], c["real"], c["ahead"]) for c in sent] == [(2, 8, 8, 1), (2, 16, 4, 1)]
    assert all(below(spans, c) == [] for d in steps for c in below(spans, d, "prefill_chunk"))  # nothing is pulled there
    attrs = [r["attrs"] for r in rounds]
    assert [a["chunk_ahead"] for a in attrs] == [0, 1, 1, 0]
    assert [a["dispatches"] for a in attrs] == [2, 3, 2, 1]  # a chunk counts where it was sent
    assert sum(s["name"] == "prefill_chunk" for s in spans) == 4  # one for uid 1, three for uid 2
    assert registry.counter_value("prefill_chunks_ahead_total") == 2 == sched.dispatch_stats()["chunks_ahead"]
    # round 1: round 0's commit 2 and metrics 1, the lookup 3: nothing was queued behind round 0's pull
    # round 2: round 1's commit 2 and metrics 1 and the loop's 4, all behind a pull the chunk covered
    # round 3: commit 2 + metrics 1 covered, up to the first token's read; from its return the register's 7
    #          and the callback's 2 are the host's gap again, as they are after a chunk pulled in its own span
    assert [a["host_gap_ms"] for a in attrs] == pytest.approx([9.0, 6.0, 0.0, 9.0], abs=1e-9)
    assert [a["covered_gap_ms"] for a in attrs] == pytest.approx([0.0, 0.0, 7.0, 3.0], abs=1e-9)
    hist = registry.histogram("host_gap_seconds")
    assert hist.count == 4 and hist.total == pytest.approx(0.024, abs=1e-12)


class SerialOrder(PagedContinuousBatchingScheduler):
    """The round as it was: no chunk is sent ahead, so every round runs its own
    chunk at its start (the order the tokens are compared with)."""

    def _send_chunk(self, ahead=False):
        return None if ahead else super()._send_chunk()


def drained(cls, engine, reqs):
    """Drain ``reqs`` through two slots (the third waits in the queue for one);
    returns the scheduler, tokens by uid, by uid the round whose decode the
    request first rode, and the number of rounds."""
    rec = FlightRecorder(span_capacity=1 << 14)
    sched = cls(engine, max_batch=2, eos_id=-1, key=jax.random.PRNGKey(7), tracer=Tracer(service="serve", recorder=rec))
    done = {}
    for req in reqs:
        sched.submit(req)
    while sched.has_work():
        done.update({c.uid: c.tokens for c in sched.step()})
    spans, by_id, rounds, _ = round_spans(rec)
    first_round = {
        s["attrs"]["uid"]: by_id[s["parent_id"]]["attrs"]["round"] for s in spans if s["name"] == "first_token"
    }
    return sched, done, first_round, len(rounds)


def test_a_chunk_sent_ahead_gives_the_same_tokens_in_the_same_rounds(engine):
    reqs = [
        Request(uid=1, prompt=[3, 1, 4, 1, 5], max_new_tokens=12),
        Request(uid=2, prompt=list(range(30, 51)), max_new_tokens=5, temperature=0.9, top_p=0.9),  # three chunks
        Request(uid=3, prompt=list(range(60, 73)), max_new_tokens=6),  # two chunks, admitted when uid 2 or 1 retires
    ]
    serial, want, want_rounds, n_serial = drained(SerialOrder, engine, reqs)
    sched, got, got_rounds, n_rounds = drained(PagedContinuousBatchingScheduler, engine, reqs)
    assert serial.dispatch_stats()["chunks_ahead"] == 0 and sched.dispatch_stats()["chunks_ahead"] >= 3
    assert got == want and all(len(got[r.uid]) == r.max_new_tokens for r in reqs)
    assert got_rounds == want_rounds and n_rounds == n_serial  # each first token rides the round it rode
    assert sched.dispatch_stats()["model_dispatches"] == serial.dispatch_stats()["model_dispatches"]
    assert sched.allocator.free_pages == serial.allocator.free_pages


@pytest.mark.parametrize("how", ["cancel", "deadline"])
@pytest.mark.parametrize("chunk", ["more_to_go", "ends_the_prompt"])
def test_a_request_gone_with_its_chunk_in_flight_returns_every_page(engine, how, chunk):
    sched = PagedContinuousBatchingScheduler(engine, max_batch=2, eos_id=-1, key=jax.random.PRNGKey(0))
    free = sched.allocator.free_pages
    alone = PagedContinuousBatchingScheduler(engine, max_batch=2, eos_id=-1, key=jax.random.PRNGKey(0))
    stays = Request(uid=1, prompt=[5, 6, 7, 8, 9], max_new_tokens=8)
    want = alone.run([stays])[1].tokens
    done = {}
    sched.submit(stays)
    sched.step()
    sched.submit(
        Request(uid=2, prompt=list(range(1, 21 if chunk == "more_to_go" else 13)), max_new_tokens=4),
        deadline=time.monotonic() + 600.0,
    )
    sched.step()  # uid 2's first chunk, and its second behind the decode
    assert sched._ahead.slot.request.uid == 2 and (sched._ahead.first is None) == (chunk == "more_to_go")
    if how == "cancel":
        assert sched.cancel(2).finish_reason == "cancelled"
    else:
        sched._slots[1].deadline = time.monotonic() - 1.0  # the next round's admit expires it
    while sched.has_work():
        done.update({c.uid: c for c in sched.step()})
    if how == "deadline":
        assert done[2].finish_reason == "timeout" and done[2].tokens == []
    assert sched._ahead is None and done[1].finish_reason == "length" and done[1].tokens == want
    assert sched.allocator.free_pages == free and sched.allocator.used_pages == 0
    # the slot and its pages serve the next request as any freed ones do
    again = sched.run([Request(uid=3, prompt=list(range(1, 21)), max_new_tokens=4)])[3]
    assert again.tokens == alone.run([Request(uid=3, prompt=list(range(1, 21)), max_new_tokens=4)])[3].tokens
    assert sched.allocator.used_pages == 20 // 8  # what the prefix cache keeps of uid 3's prompt


def test_packed_rounds_count_the_same_gap():
    from tests.test_packed import make_engine

    engine, _ = make_engine(TINY_LLAMA)
    clock, rec = Clock(), FlightRecorder()
    sched = PagedContinuousBatchingScheduler(
        engine, max_batch=2, eos_id=-1, key=jax.random.PRNGKey(0), packed=True,
        tracer=Tracer(service="serve", recorder=rec, clock=clock),
    )
    on_token = costing(clock, 2 * MS, lambda uid, token, index: None)
    sched.submit(Request(uid=1, prompt=[1, 2, 3, 4, 5], max_new_tokens=3), on_token=on_token)
    while sched.has_work():
        sched.step()
        clock.advance(4 * MS)
    _, _, rounds, children = round_spans(rec)
    # every round commits one token (2 ms) and the loop takes 4: what the next round's enqueue finds
    assert [r["attrs"]["host_gap_ms"] for r in rounds] == pytest.approx([0.0, 6.0, 6.0], abs=1e-9)
    assert all(names == ["admit", "decode_prep", "decode_step", "commit", "round_metrics"] for names in children.values())


# -- the server's loop, the stream's writes and /metrics ------------------------

#: what ``/metrics`` rendered after one request at the parent commit (030354a)
PARENT_SERIES = {
    "active_slots", "batch_fill", "decode_live_page_share", "decode_seconds", "decode_step_seconds",
    "dispatch_tokens_real_total", "dispatch_tokens_total", "dispatches_per_round", "draining",
    "e2e_latency_seconds", "http_requests_total", "kv_bytes_per_token", "kv_cache_bytes", "kv_pages_free",
    "kv_pages_used", "migrated_inserts_total", "migration_bytes_total", "migration_failures_total",
    "model_dispatches_total", "packed_token_utilization", "pages_migrated_total", "param_bytes",
    "prefill_pad_share", "prefill_seconds", "prefill_stall_share", "prefix_cache_hit_rate",
    "prefix_fetch_failures_total", "prefix_fetch_total", "queue_depth", "queue_wait_seconds",
    "requests_finished_total", "retry_after_s", "sample_draws_total", "sched_rounds_total", "sse_flush_seconds",
    "tokens_generated_total", "tokens_per_dispatch", "tpot_seconds", "ttft_seconds", "warming", "weights_version",
}


def test_the_loop_has_spans_a_stream_one_flush_span_and_metrics_one_more_series(engine):
    rec = FlightRecorder()
    sched = PagedContinuousBatchingScheduler(engine, max_batch=2, eos_id=-1, key=jax.random.PRNGKey(0))
    server = GenerateServer(sched, port=0, max_queue=4, tracer=Tracer(service="serve", recorder=rec))
    thread = threading.Thread(
        target=lambda: asyncio.run(server.serve_forever(install_signal_handlers=False)), daemon=True
    )
    thread.start()
    assert server.started.wait(60), "server failed to start"
    try:
        _, _, body = _http(server.port, "POST", "/v1/generate", {"prompt": list(range(1, 13)), "max_new_tokens": 4})
        events = _sse_events(body)
        assert events[-1] == "[DONE]" and sum("token" in e for e in events[:-1]) == 4
        metrics = _http(server.port, "GET", "/metrics")[2].decode()
    finally:
        server.begin_drain()
        thread.join(60)
    assert not thread.is_alive() and server._worker_error is None

    series = set(re.findall(r"^# TYPE relora_serve_(\S+) ", metrics, re.M))
    assert series == PARENT_SERIES | {"host_gap_seconds", "prefill_chunks_ahead_total"}
    rounds = int(re.search(r"^relora_serve_sched_rounds_total (\d+)", metrics, re.M).group(1))
    assert int(re.search(r"^relora_serve_host_gap_seconds_count (\d+)", metrics, re.M).group(1)) == rounds

    # every streamed token's write is timed; only the first, the last hop of the TTFT, leaves a span
    assert int(re.search(r"^relora_serve_sse_flush_seconds_count (\d+)", metrics, re.M).group(1)) == 4
    spans = rec.spans()
    flushes = [s for s in spans if s["name"] == "sse_flush"]
    root = next(s for s in spans if s["name"] == "request")
    assert [(s["attrs"]["index"], s["parent_id"]) for s in flushes] == [(0, root["span_id"])]

    # the loop's own part of the gap: on the model thread, outside every round
    round_thread = {s["thread"] for s in spans if s["name"] == "round"}
    assert round_thread == {"serve-model"}
    loop_spans = [s for s in spans if s["name"] in ("claim", "flush_outbox")]
    assert {s["name"] for s in loop_spans} == {"claim", "flush_outbox"}
    assert all(s["parent_id"] is None and s["thread"] == "serve-model" for s in loop_spans)
    assert sum(s["attrs"]["claimed"] for s in loop_spans if s["name"] == "claim") == 1
    # a turn per step and none for the idle turns after the request was done
    n_steps = sum(s["name"] == "flush_outbox" for s in loop_spans)
    assert n_steps <= sum(s["name"] == "claim" for s in loop_spans) <= n_steps + 1
