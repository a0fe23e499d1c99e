"""The host's part of a serving round, counted where it happens
(docs/observability.md, "The host gap"): the scheduler's own count of the
time between a blocking pull's return and the next enqueue, the spans that
name every part of it, and what the tracing no longer records."""

import asyncio
import re
import threading

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
    assert series == PARENT_SERIES | {"host_gap_seconds"}
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
