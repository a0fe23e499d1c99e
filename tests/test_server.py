"""HTTP front-end tests: the asyncio server over the incremental scheduler.

Covers the serving acceptance criteria on CPU with a tiny model:

- streamed SSE output is token-identical to ``scheduler.run()`` for the same
  (uid, key) — HTTP adds transport, not nondeterminism;
- a full admission queue answers 429 + Retry-After while in-flight streams
  keep going (bounded memory under overload);
- ``deadline_s`` expiry mid-decode returns the partial output with
  ``finish_reason: "timeout"``;
- a client disconnect frees the decode slot for the next request;
- drain (the SIGTERM handler's body; the real signal is exercised by
  scripts/smoke_test.sh) finishes in-flight work, 503s new work, and stops
  the server.

The server runs in a background thread (signal handlers off — they need the
main thread's loop); clients are raw close-delimited HTTP/1.1 sockets, so
these tests pin the exact wire format the stdlib front-end speaks.
"""

import asyncio
import json
import socket
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.params_util import init_params
from relora_tpu.serve.engine import InferenceEngine, build_decode_model
from relora_tpu.serve.scheduler import ContinuousBatchingScheduler, Request
from relora_tpu.serve.server import BadRequest, GenerateServer, parse_generate_body

pytestmark = pytest.mark.serve

TINY = ModelConfig(
    family="llama",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=160,
    num_hidden_layers=2,
    num_attention_heads=4,
    max_sequence_length=512,
)
CACHE = 512


@pytest.fixture(scope="module")
def engine():
    model = build_decode_model(TINY, cache_size=CACHE)
    base = type(model)(TINY, lora=None, dtype=jnp.float32, scan_layers=True)
    params = init_params(base, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return InferenceEngine(TINY, params, cache_size=CACHE)


class _Server:
    """Run a GenerateServer in a background thread for the duration of a test.

    Exit drains (idempotent if the test already drained) and asserts the
    model thread did not die — a worker exception fails the test instead of
    hanging it."""

    def __init__(self, engine, *, max_batch=1, max_queue=4, key_seed=0, **kwargs):
        self.scheduler = ContinuousBatchingScheduler(
            engine, max_batch=max_batch, key=jax.random.PRNGKey(key_seed)
        )
        self.server = GenerateServer(
            self.scheduler, port=0, max_queue=max_queue, **kwargs
        )
        self.thread = threading.Thread(
            target=lambda: asyncio.run(
                self.server.serve_forever(install_signal_handlers=False)
            ),
            daemon=True,
        )

    def __enter__(self) -> GenerateServer:
        self.thread.start()
        assert self.server.started.wait(60), "server failed to start"
        return self.server

    def __exit__(self, *exc):
        self.server.begin_drain()
        self.thread.join(60)
        assert not self.thread.is_alive(), "server did not drain within 60s"
        assert self.server._worker_error is None, repr(self.server._worker_error)


# -- raw HTTP/1.1 clients (close-delimited, like the server speaks) -----------


def _request_bytes(method: str, path: str, body: bytes) -> bytes:
    head = (
        f"{method} {path} HTTP/1.1\r\nHost: test\r\n"
        f"Content-Length: {len(body)}\r\n\r\n"
    )
    return head.encode() + body


def _parse_response(data: bytes):
    head, _, rest = data.partition(b"\r\n\r\n")
    lines = head.split(b"\r\n")
    status = int(lines[0].split(b" ", 2)[1])
    headers = {}
    for line in lines[1:]:
        key, _, value = line.decode("latin-1").partition(":")
        headers[key.strip().lower()] = value.strip()
    return status, headers, rest


def _http(port: int, method: str, path: str, body=None, timeout=60.0):
    """One request, read to EOF (the server closes every connection)."""
    payload = b"" if body is None else (
        body if isinstance(body, bytes) else json.dumps(body).encode()
    )
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as sock:
        sock.sendall(_request_bytes(method, path, payload))
        data = b""
        while True:
            chunk = sock.recv(65536)
            if not chunk:
                break
            data += chunk
    return _parse_response(data)


def _sse_events(body: bytes):
    events = []
    for block in body.decode().split("\n\n"):
        block = block.strip()
        if not block.startswith("data: "):
            continue
        payload = block[len("data: "):]
        events.append("[DONE]" if payload == "[DONE]" else json.loads(payload))
    return events


def _generate(port: int, payload: dict):
    """POST /v1/generate and split the SSE stream into (tokens, final record)."""
    status, headers, body = _http(port, "POST", "/v1/generate", payload)
    assert status == 200, body
    events = _sse_events(body)
    assert events[-1] == "[DONE]"
    final = events[-2]
    token_events = events[:-2]
    assert [e["index"] for e in token_events] == list(range(len(token_events)))
    return [e["token"] for e in token_events], final


class _Stream:
    """An open streaming request: read SSE events one at a time, or hang up
    mid-stream (the disconnect / overload tests)."""

    def __init__(self, port: int, payload: dict, timeout=60.0):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=timeout)
        self.sock.sendall(
            _request_bytes("POST", "/v1/generate", json.dumps(payload).encode())
        )
        self.buf = b""
        head = self._read_until(b"\r\n\r\n")
        assert head is not None, "no response head"
        self.status = int(head.split(b" ", 2)[1])

    def _read_until(self, marker: bytes):
        while marker not in self.buf:
            chunk = self.sock.recv(4096)
            if not chunk:
                return None
            self.buf += chunk
        idx = self.buf.index(marker) + len(marker)
        out, self.buf = self.buf[:idx], self.buf[idx:]
        return out

    def next_event(self):
        block = self._read_until(b"\n\n")
        if block is None:
            return None
        text = block.decode().strip()
        assert text.startswith("data: "), text
        payload = text[len("data: "):]
        return "[DONE]" if payload == "[DONE]" else json.loads(payload)

    def read_to_done(self):
        events = []
        while True:
            event = self.next_event()
            assert event is not None, "stream ended before [DONE]"
            if event == "[DONE]":
                return events
            events.append(event)

    def close(self):
        self.sock.close()


def _solo_tokens(engine, uid: int, payload: dict, key_seed: int):
    """Reference: the same request alone through scheduler.run()."""
    sched = ContinuousBatchingScheduler(
        engine, max_batch=1, key=jax.random.PRNGKey(key_seed)
    )
    req = Request(
        uid=uid,
        prompt=payload["prompt"],
        max_new_tokens=payload["max_new_tokens"],
        temperature=payload.get("temperature", 0.0),
        top_p=payload.get("top_p", 1.0),
    )
    return sched.run([req])[uid].tokens


# -- request validation (no engine) -------------------------------------------


def test_parse_generate_body_validation():
    fields = parse_generate_body(
        json.dumps({"prompt": [1, 2, 3]}).encode(),
        default_max_new_tokens=8,
        default_temperature=0.5,
        default_top_p=0.9,
    )
    assert fields["prompt"] == [1, 2, 3]
    assert fields["max_new_tokens"] == 8
    assert fields["temperature"] == 0.5
    assert fields["top_p"] == 0.9
    assert fields["stream"] is True
    assert fields["deadline_s"] is None
    assert fields["spec"] is True  # per-request opt-out defaults to on

    opted_out = parse_generate_body(
        json.dumps({"prompt": [1], "spec": False}).encode(),
        default_max_new_tokens=8,
        default_temperature=0.0,
        default_top_p=1.0,
    )
    assert opted_out["spec"] is False

    bad = [
        b"not json",
        b"[1, 2]",
        json.dumps({}).encode(),
        json.dumps({"prompt": "text"}).encode(),
        json.dumps({"prompt": [1, True]}).encode(),
        json.dumps({"prompt": [1], "max_new_tokens": 0}).encode(),
        json.dumps({"prompt": [1], "temperature": -0.1}).encode(),
        json.dumps({"prompt": [1], "top_p": 0.0}).encode(),
        json.dumps({"prompt": [1], "top_p": 1.5}).encode(),
        json.dumps({"prompt": [1], "stream": "yes"}).encode(),
        json.dumps({"prompt": [1], "deadline_s": -1}).encode(),
        json.dumps({"prompt": [1], "spec": "on"}).encode(),
    ]
    for body in bad:
        with pytest.raises(BadRequest):
            parse_generate_body(
                body, default_max_new_tokens=8, default_temperature=0.0, default_top_p=1.0
            )


# -- determinism over HTTP ----------------------------------------------------


def test_streamed_tokens_match_scheduler_run(engine):
    """Acceptance: concurrent sampled HTTP streams produce exactly the tokens
    ``scheduler.run()`` produces for the same (uid, key) — batch composition
    and transport change nothing."""
    key_seed = 7
    payloads = [
        {"prompt": [1 + i, 2, 3], "max_new_tokens": 6, "temperature": 0.9}
        for i in range(3)
    ]
    results = {}

    def post(port, payload):
        tokens, final = _generate(port, payload)
        results[final["uid"]] = (payload, tokens, final)

    with _Server(engine, max_batch=2, max_queue=4, key_seed=key_seed) as server:
        threads = [
            threading.Thread(target=post, args=(server.port, p)) for p in payloads
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    assert sorted(results) == [0, 1, 2]
    for uid, (payload, tokens, final) in results.items():
        assert final["finish_reason"] == "length"
        assert final["tokens"] == tokens, "stream diverged from the finish record"
        assert tokens == _solo_tokens(engine, uid, payload, key_seed)


def test_unary_response_matches_scheduler_run(engine):
    payload = {"prompt": [9, 8, 7], "max_new_tokens": 5, "stream": False}
    with _Server(engine, max_batch=1, key_seed=3) as server:
        status, _, body = _http(server.port, "POST", "/v1/generate", payload)
    assert status == 200
    record = json.loads(body)
    assert record["finish_reason"] == "length"
    assert record["tokens"] == _solo_tokens(engine, record["uid"], payload, 3)


# -- error paths and introspection endpoints ----------------------------------


def test_http_error_paths_and_endpoints(engine):
    with _Server(engine, max_batch=1) as server:
        port = server.port
        status, _, body = _http(port, "POST", "/v1/generate", b"not json")
        assert status == 400 and b"JSON" in body
        status, _, body = _http(port, "POST", "/v1/generate", {"prompt": []})
        assert status == 400 and b"prompt" in body
        # capacity violations surface as 400 before admission, not as a
        # decode-loop crash later
        status, _, body = _http(
            port, "POST", "/v1/generate",
            {"prompt": [1] * 16, "max_new_tokens": CACHE},
        )
        assert status == 400 and b"cache entries" in body
        status, _, _ = _http(port, "GET", "/v1/generate")
        assert status == 405
        status, _, _ = _http(port, "GET", "/no/such/route")
        assert status == 404
        # malformed request line -> 400, not a hung connection
        with socket.create_connection(("127.0.0.1", port), timeout=10) as sock:
            sock.sendall(b"garbage\r\n\r\n")
            data = b""
            while True:
                chunk = sock.recv(4096)
                if not chunk:
                    break
                data += chunk
        assert b"400" in data.split(b"\r\n", 1)[0]

        status, _, body = _http(port, "GET", "/healthz")
        assert status == 200
        health = json.loads(body)
        assert health["status"] == "ok"
        assert health["max_batch"] == 1 and health["max_queue"] == 4
        status, _, body = _http(port, "GET", "/metrics")
        assert status == 200
        text = body.decode()
        assert 'relora_serve_http_requests_total{route="healthz"} 1' in text
        assert 'relora_serve_rejected_total{reason="bad_request"}' in text


# -- flow control -------------------------------------------------------------


def test_overload_sheds_load_with_429(engine):
    """max_batch=1 + max_queue=1: one request decoding, one waiting; the
    third is rejected with 429 + Retry-After while the first keeps
    streaming — in-system work stays bounded under overload."""
    with _Server(engine, max_batch=1, max_queue=1, retry_after_s=2.0) as server:
        port = server.port
        a = _Stream(port, {"prompt": [1, 2], "max_new_tokens": 300})
        assert a.status == 200
        first = a.next_event()
        assert first["index"] == 0  # A holds the decode slot
        b = _Stream(port, {"prompt": [3, 4], "max_new_tokens": 50})
        assert b.status == 200  # B accepted: it fills the admission queue

        status, headers, body = _http(
            port, "POST", "/v1/generate", {"prompt": [5, 6], "max_new_tokens": 4}
        )
        assert status == 429, body
        assert headers.get("retry-after") == "2"
        assert b"admission queue full" in body

        # the reject did not disturb the in-flight stream
        assert a.next_event()["token"] is not None

        status, _, body = _http(port, "GET", "/metrics")
        assert 'relora_serve_rejected_total{reason="queue_full"} 1' in body.decode()
        a.close()
        b.close()


def test_deadline_expiry_returns_partial_output(engine):
    """A request that cannot finish inside deadline_s stops at a step
    boundary with its partial tokens and finish_reason "timeout"."""
    with _Server(engine, max_batch=1) as server:
        tokens, final = _generate(
            server.port,
            {"prompt": [1, 2, 3], "max_new_tokens": 480, "deadline_s": 0.25},
        )
    assert final["finish_reason"] == "timeout"
    assert 0 < len(tokens) < 480
    assert final["tokens"] == tokens


def test_client_disconnect_frees_slot(engine):
    """Hanging up mid-stream cancels the request at the next step boundary:
    the slot frees, metrics record the disconnect, and the next request gets
    the slot."""
    with _Server(engine, max_batch=1) as server:
        port = server.port
        a = _Stream(port, {"prompt": [1, 2], "max_new_tokens": 400})
        assert a.next_event()["index"] == 0
        a.close()

        deadline = time.monotonic() + 30.0
        freed = False
        while time.monotonic() < deadline:
            _, _, body = _http(port, "GET", "/metrics")
            text = body.decode()
            if (
                'relora_serve_requests_finished_total{reason="cancelled"} 1' in text
                and "relora_serve_active_slots 0" in text
            ):
                freed = True
                break
            time.sleep(0.05)
        assert freed, "slot was not freed after client disconnect"
        assert "relora_serve_disconnects_total 1" in text

        tokens, final = _generate(port, {"prompt": [7, 8], "max_new_tokens": 4})
        assert final["finish_reason"] == "length" and len(tokens) == 4


def test_drain_finishes_in_flight_and_rejects_new(engine):
    """begin_drain (the SIGTERM handler's body): in-flight streams run to
    completion, new requests get 503 + Retry-After, /healthz flips to
    draining, and serve_forever returns."""
    holder = _Server(engine, max_batch=1)
    with holder as server:
        port = server.port
        a = _Stream(port, {"prompt": [1, 2], "max_new_tokens": 60})
        assert a.next_event()["index"] == 0

        server.begin_drain()
        status, _, body = _http(port, "GET", "/healthz")
        assert status == 503 and json.loads(body)["status"] == "draining"
        status, headers, _ = _http(
            port, "POST", "/v1/generate", {"prompt": [9], "max_new_tokens": 2}
        )
        assert status == 503 and "retry-after" in headers

        events = a.read_to_done()
        final = events[-1]
        assert final["finish_reason"] == "length"
        assert len(final["tokens"]) == 60
        a.close()
        assert server.drained.wait(60), "model thread did not exit after drain"
        holder.thread.join(60)
        assert not holder.thread.is_alive(), "serve_forever did not return"


def test_request_id_header_and_span_propagation(engine):
    """One request id threads the whole stack: the client's X-Request-Id
    becomes the span trace_id on every phase (request, queue_wait, prefill,
    insert, decode, sse_flush) and is echoed on the response; without the
    header the server mints one."""
    from relora_tpu.obs.flight import FlightRecorder
    from relora_tpu.obs.tracer import Tracer

    recorder = FlightRecorder()
    tracer = Tracer(service="serve", recorder=recorder)
    with _Server(engine, tracer=tracer) as server:
        port = server.port
        rid = "feedfacecafebeef"
        head = (
            "POST /v1/generate HTTP/1.1\r\nHost: test\r\n"
            f"X-Request-Id: {rid}\r\n"
        )
        payload = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 4}).encode()
        with socket.create_connection(("127.0.0.1", port), timeout=60) as sock:
            sock.sendall(
                head.encode() + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                + payload
            )
            data = b""
            while True:
                chunk = sock.recv(65536)
                if not chunk:
                    break
                data += chunk
        status, headers, body = _parse_response(data)
        assert status == 200
        assert headers["x-request-id"] == rid
        events = _sse_events(body)
        assert events[-1] == "[DONE]" and events[-2]["finish_reason"] == "length"

        # the root "request" span ends in the finish callback on the event
        # loop — give it a moment to land in the recorder
        deadline = time.monotonic() + 10.0
        spans = {}
        while time.monotonic() < deadline:
            spans = {
                s["name"]: s for s in recorder.spans() if s["trace_id"] == rid
            }
            if "request" in spans:
                break
            time.sleep(0.02)
        assert {
            "request", "queue_wait", "prefill", "insert", "decode", "sse_flush"
        } <= set(spans)
        root = spans["request"]
        assert root["parent_id"] is None
        assert root["attrs"]["finish_reason"] == "length"
        # cross-thread spans carry an explicit parent link to the root
        assert spans["queue_wait"]["parent_id"] == root["span_id"]
        assert spans["sse_flush"]["parent_id"] == root["span_id"]
        # model-thread phases ran off the HTTP thread but share the trace
        assert spans["prefill"]["thread"] != root["thread"]

        # no header -> the server mints a fresh 16-hex id and echoes it
        status2, headers2, _ = _http(
            port, "POST", "/v1/generate", {"prompt": [5], "max_new_tokens": 2}
        )
        assert status2 == 200
        rid2 = headers2["x-request-id"]
        assert rid2 != rid and len(rid2) == 16
        int(rid2, 16)  # hex


# -- dynamic Retry-After ------------------------------------------------------


def test_dynamic_retry_after_tracks_queue_and_tpot():
    """The Retry-After hint is the time for the current queue to clear at
    the observed decode rate (depth x rolling TPOT), clamped to
    [max(1, floor), 30]; a cold server falls back to the configured floor."""
    from relora_tpu.serve.admission import AdmissionController, Ticket

    def _ticket(uid):
        return Ticket(
            uid=uid,
            request=Request(uid=uid, prompt=[1], max_new_tokens=1),
            deadline=None,
            on_token=lambda *_: None,
            on_finish=lambda *_: None,
        )

    adm = AdmissionController(8, retry_after_s=2.0)
    assert adm.retry_after_s == 2.0  # cold: the old fixed behaviour
    adm.note_tpot(0.5)
    assert adm.retry_after_s == 2.0  # empty queue: floor still rules
    for uid in range(6):
        adm.try_admit(_ticket(uid))
    assert adm.retry_after_s == pytest.approx(6 * 0.5)  # depth x TPOT
    adm.note_tpot(10.0)  # EWMA folds 0.8/0.2 -> 2.4 s/token
    assert adm.retry_after_s == pytest.approx(6 * 2.4)
    adm.note_tpot(100.0)  # estimate explodes past the cap
    assert adm.retry_after_s == AdmissionController.RETRY_AFTER_CAP_S
    adm.note_tpot(-1.0)  # nonsense observations are ignored
    assert adm.retry_after_s == AdmissionController.RETRY_AFTER_CAP_S

    # sub-second floors round up to 1s: "Retry-After: 0" helps nobody
    assert AdmissionController(8, retry_after_s=0.2).retry_after_s == 1.0


# -- self-diagnosis drills (fault-injected) -----------------------------------

from relora_tpu.utils import faults  # noqa: E402


@pytest.fixture
def disarm_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.mark.faults
def test_model_thread_death_fails_all_requests(engine, disarm_faults):
    """An exception on the model thread (injected ``serve_decode``) must
    terminally complete every in-flight and queued request with
    ``finish_reason="error"`` — not strand their streams — and flip
    /healthz to 503 "error" while the listener lingers."""
    faults.configure("serve_decode", exc=RuntimeError, at_token=4)
    scheduler = ContinuousBatchingScheduler(
        engine, max_batch=2, key=jax.random.PRNGKey(11)
    )
    server = GenerateServer(scheduler, port=0, max_queue=4, error_linger_s=8.0)

    def run():
        try:
            asyncio.run(server.serve_forever(install_signal_handlers=False))
        except RuntimeError:
            pass  # serve_forever re-raises the worker death; expected here

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    assert server.started.wait(60), "server failed to start"
    port = server.port
    a = _Stream(port, {"prompt": [1, 2], "max_new_tokens": 50})
    b = _Stream(port, {"prompt": [3, 4], "max_new_tokens": 50})
    assert a.status == 200 and b.status == 200
    for stream in (a, b):
        events = stream.read_to_done()  # [DONE] still arrives: typed failure
        final = events[-1]
        assert final["finish_reason"] == "error"
        assert "model thread died" in final["error"]
        assert "injected fault at 'serve_decode'" in final["error"]
    a.close()
    b.close()

    # the listener lingers so probes see *why* it is about to exit
    status, _, body = _http(port, "GET", "/healthz")
    health = json.loads(body)
    assert status == 503 and health["status"] == "error"
    assert "injected fault" in health["detail"]
    # new work fails fast instead of queueing behind a dead worker
    status, _, body = _http(
        port, "POST", "/v1/generate", {"prompt": [5], "max_new_tokens": 2}
    )
    assert status == 500 and b"model thread died" in body
    status, _, body = _http(port, "GET", "/metrics")
    text = body.decode()
    assert "relora_serve_model_dead 1" in text
    assert 'relora_serve_requests_finished_total{reason="error"} 2' in text

    thread.join(60)
    assert not thread.is_alive(), "server did not shut down after worker death"
    assert isinstance(server._worker_error, RuntimeError)


@pytest.mark.faults
def test_stall_watchdog_flips_healthz_and_recovers(
    engine, disarm_faults, tmp_path, monkeypatch
):
    """No decode progress for stall_timeout_s (injected ``serve_stall``)
    flips /healthz to 503 "stuck" and dumps the flight recorder; when the
    decode loop resumes, the replica un-sticks by itself."""
    monkeypatch.setenv("RELORA_TPU_FLIGHT_DIR", str(tmp_path))
    faults.configure("serve_stall", sleep_s=1.5, at_token=2)
    with _Server(engine, max_batch=1, stall_timeout_s=0.3) as server:
        port = server.port
        a = _Stream(port, {"prompt": [1, 2], "max_new_tokens": 30})
        assert a.status == 200

        saw = None
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            status, _, body = _http(port, "GET", "/healthz")
            saw = (status, json.loads(body))
            if status == 503 and saw[1]["status"] == "stuck":
                break
            time.sleep(0.03)
        assert saw is not None and saw[1]["status"] == "stuck", saw
        assert "no decode step" in saw[1]["detail"]

        # the stall ends; the stream still finishes in full
        events = a.read_to_done()
        assert events[-1]["finish_reason"] == "length"
        assert len(events[-1]["tokens"]) == 30
        a.close()

        recovered = False
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            status, _, body = _http(port, "GET", "/healthz")
            if status == 200 and json.loads(body)["status"] == "ok":
                recovered = True
                break
            time.sleep(0.03)
        assert recovered, "healthz never recovered after the stall"
        _, _, body = _http(port, "GET", "/metrics")
        assert "relora_serve_stuck 0" in body.decode()

    dumps = list(tmp_path.glob("flight_serve_stall_*.json"))
    assert dumps, "watchdog did not dump the flight recorder"
    dump = json.loads(dumps[0].read_text())
    assert dump["reason"] == "serve_stall"


@pytest.mark.faults
def test_accept_drop_closes_connection_then_recovers(engine, disarm_faults):
    """``serve_accept_drop``: the first accepted connection dies with zero
    response bytes (what a router's pre-stream retry must absorb); the next
    one is served normally."""
    faults.configure("serve_accept_drop", times=1)
    with _Server(engine, max_batch=1) as server:
        port = server.port
        with socket.create_connection(("127.0.0.1", port), timeout=10) as sock:
            sock.sendall(_request_bytes("GET", "/healthz", b""))
            # closed with zero response bytes: clean EOF or RST (the server
            # hung up with our request unread), never a served response
            try:
                assert sock.recv(4096) == b"", "dropped connection sent data"
            except ConnectionResetError:
                pass
        status, _, _ = _http(port, "GET", "/healthz")
        assert status == 200
        status, _, body = _http(port, "GET", "/metrics")
        assert "relora_serve_accept_drops_total 1" in body.decode()


def test_a_steps_stream_events_cross_to_the_loop_together(engine):
    """Four streams decode side by side: what a scheduler step posts — a token
    a row, a finish — goes to the event loop in one hand-over when the step
    ends, not one wake-up a row, and every stream still gets its own tokens
    in order."""
    with _Server(engine, max_batch=4, max_queue=8) as server:
        posted, flushed = [], []
        real_post, real_flush = server._post, server._flush_outbox

        def counting_post(loop, events, item):
            posted.append(item[0])
            real_post(loop, events, item)

        def counting_flush():
            if server._outbox:
                flushed.append(len(server._outbox))
            real_flush()

        server._post, server._flush_outbox = counting_post, counting_flush
        payloads = [{"prompt": [3 + i, 5, 7], "max_new_tokens": 12, "stream": True} for i in range(4)]
        results = [None] * 4

        def client(i):
            results[i] = _generate(server.port, payloads[i])

        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        for tokens, final in results:
            assert len(tokens) == 12 and final["tokens"] == tokens
        assert posted.count("token") == 48 and posted.count("finish") == 4
        # far fewer hand-overs than events, and some carried several rows' tokens
        assert sum(flushed) == 52 and len(flushed) < 40 and max(flushed) >= 2
