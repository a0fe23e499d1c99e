"""Async HTTP/1.1 serving front-end: streaming generation over the scheduler.

Stdlib-only (asyncio + sockets, like the analysis package keeps to ast): one
listener accepts requests while a dedicated **model thread** drives the
blocking jitted engine through the scheduler's incremental core — the decode
loop never blocks the event loop, and the event loop never touches jax.

Endpoints:

- ``POST /v1/generate`` — body ``{"prompt": [ids...], "max_new_tokens": N,
  "temperature": T, "top_p": P, "stream": true, "deadline_s": S}``.
  Streaming responses are Server-Sent Events (``text/event-stream``): one
  ``data: {"uid", "index", "token"}`` event per token as it is sampled, a
  final ``data: {...finish record...}`` with the full token list and
  latency fields, then ``data: [DONE]``.  ``"stream": false`` returns the
  finish record as a single JSON body.
- ``GET /healthz`` — readiness: 200 while accepting; 503 with ``status``
  ``"draining"`` (SIGTERM), ``"stuck"`` (stall watchdog: no decode step for
  ``stall_timeout_s``), ``"error"`` (model thread died), or ``"warming"``
  (``warmup_fn`` still paying compile buckets: the replica is discoverable
  but not yet routable) — the router (serve/router.py) ejects a replica on
  any 503 and (re-)adopts it when the status clears.  Paged schedulers attach a ``paging`` block (pool
  pressure, prefix-cache stats, and — under ``paging.dispatch`` — the
  dispatch-economics counters: dispatches per round, tokens per dispatch,
  and packed-token utilization when ``--packed`` is on).
- ``GET /metrics`` — Prometheus text exposition (serve/admission.ServeMetrics).

Flow control, end to end:

- **Backpressure**: the AdmissionController is the only waiting room; when
  its bounded queue is full new requests get **429 + Retry-After** — memory
  is fixed at ``max_batch`` decoding + ``max_queue`` waiting, no matter the
  offered load, and in-flight streams are unaffected.
- **Deadlines**: ``deadline_s`` bounds a request's wall time; the scheduler
  expires it at the next step boundary and the stream finishes with its
  partial output and ``finish_reason: "timeout"``.
- **Disconnects**: a client that goes away mid-stream flips the ticket's
  ``cancelled`` event; the model thread cancels the request at the next
  step boundary, freeing the slot for the next admission.
- **Graceful drain**: SIGTERM (or ``begin_drain()``) stops admissions (new
  requests get **503**), finishes everything in flight *and* everything
  already queued, then shuts the listener down — the update-boundary
  pattern from train/resilience.PreemptionGuard, with the decode step as
  the boundary.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import threading
import time
import zlib
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Set, Tuple
from urllib.parse import urlsplit

from relora_tpu.obs.flight import dump_on_fault
from relora_tpu.obs.tracer import NoopTracer, Tracer, new_trace_id
from relora_tpu.serve import disagg
from relora_tpu.serve.admission import (
    AdmissionController,
    Draining,
    QueueFull,
    ServeMetrics,
    Ticket,
)
from relora_tpu.serve.scheduler import (
    Completion,
    ContinuousBatchingScheduler,
    Request,
)
from relora_tpu.serve.wire import (
    decode_page_run as _decode_page_run,
    encode_page_run as _encode_page_run,
    head as _head,
    read_http_request as _read_http_request,
    respond as _respond,
    respond_json as _respond_json,
    sse as _sse,
)
from relora_tpu.utils import faults
from relora_tpu.utils.logging import MetricsLogger, get_logger

logger = get_logger(__name__)

_REQUEST_TIMEOUT_S = 30.0
_IDLE_POP_S = 0.02


def _completion_record(completion: Completion) -> Dict[str, Any]:
    record = {
        "uid": completion.uid,
        "finish_reason": completion.finish_reason,
        "tokens": completion.tokens,
        "prompt_tokens": completion.prompt_tokens,
        "output_tokens": len(completion.tokens),
        "ttft_s": round(completion.ttft_s, 6),
        "latency_s": round(completion.latency_s, 6),
    }
    if completion.error is not None:
        record["error"] = completion.error
    return record


class BadRequest(Exception):
    """Malformed request body — HTTP 400."""


class _ReloadRequest:
    """One pending in-place weight reload, handed to the model thread.

    ``apply`` is the prepared host->device closure (the checkpoint is already
    verified and restored to host memory when this exists); the model thread
    runs it at an idle decode boundary and completes ``done`` with ``ok`` /
    ``error`` filled in.
    """

    def __init__(self, apply: Callable[[], None], version: int, checkpoint: str):
        self.apply = apply
        self.version = version
        self.checkpoint = checkpoint
        self.done = threading.Event()
        self.ok = False
        self.error: Optional[str] = None


def parse_generate_body(
    body: bytes,
    *,
    default_max_new_tokens: int,
    default_temperature: float,
    default_top_p: float,
) -> Dict[str, Any]:
    """Validate the /v1/generate JSON body into plain fields (no uid yet).
    Raises BadRequest with a reader-facing message on any violation."""
    try:
        payload = json.loads(body.decode("utf-8") or "{}")
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise BadRequest(f"body is not valid JSON: {e}") from None
    if not isinstance(payload, dict):
        raise BadRequest("body must be a JSON object")
    prompt = payload.get("prompt")
    if not isinstance(prompt, list) or not all(
        isinstance(t, int) and not isinstance(t, bool) for t in prompt
    ):
        raise BadRequest('"prompt" must be a list of token ids (ints)')
    max_new = payload.get("max_new_tokens", default_max_new_tokens)
    if not isinstance(max_new, int) or isinstance(max_new, bool) or max_new < 1:
        raise BadRequest('"max_new_tokens" must be an int >= 1')
    temperature = payload.get("temperature", default_temperature)
    top_p = payload.get("top_p", default_top_p)
    if not isinstance(temperature, (int, float)) or temperature < 0:
        raise BadRequest('"temperature" must be a number >= 0')
    if not isinstance(top_p, (int, float)) or not 0.0 < top_p <= 1.0:
        raise BadRequest('"top_p" must be in (0, 1]')
    stream = payload.get("stream", True)
    if not isinstance(stream, bool):
        raise BadRequest('"stream" must be a boolean')
    deadline_s = payload.get("deadline_s")
    if deadline_s is not None and (
        not isinstance(deadline_s, (int, float)) or deadline_s <= 0
    ):
        raise BadRequest('"deadline_s" must be a number > 0')
    # per-request speculative opt-out: "spec": false skips drafting for this
    # request on a --spec server (output distribution is identical either way);
    # a no-op when the server runs without speculation
    spec = payload.get("spec", True)
    if not isinstance(spec, bool):
        raise BadRequest('"spec" must be a boolean')
    # multi-tenant: "adapter" names a LoRA adapter dir under --adapter-dir;
    # absent/null decodes the base model.  Whether the name is servable is
    # the scheduler's call (validate_request -> registry.known)
    adapter = payload.get("adapter")
    if adapter is not None and (not isinstance(adapter, str) or not adapter.strip()):
        raise BadRequest('"adapter" must be a non-empty string')
    return {
        "prompt": prompt,
        "max_new_tokens": max_new,
        "temperature": float(temperature),
        "top_p": float(top_p),
        "stream": stream,
        "deadline_s": deadline_s,
        "spec": spec,
        "adapter": adapter.strip() if isinstance(adapter, str) else None,
    }


class GenerateServer:
    """Asyncio front-end over a ContinuousBatchingScheduler.

    The constructor takes an *idle* scheduler (the server's model thread
    becomes its single driving thread).  ``serve_forever()`` binds, starts
    the model thread, and runs until a drain completes; ``begin_drain()``
    (thread-safe, also wired to SIGTERM) initiates shutdown.
    """

    def __init__(
        self,
        scheduler: ContinuousBatchingScheduler,
        *,
        host: str = "127.0.0.1",
        port: int = 8000,
        max_queue: int = 64,
        default_max_new_tokens: int = 64,
        default_temperature: float = 0.0,
        default_top_p: float = 1.0,
        retry_after_s: float = 1.0,
        stall_timeout_s: float = 0.0,
        error_linger_s: float = 1.0,
        metrics: Optional[MetricsLogger] = None,
        tracer: Optional[Tracer] = None,
        reload_prepare: Optional[Callable[[str], Callable[[], None]]] = None,
        weights_version: int = 0,
        weights_checkpoint: str = "",
        warmup_fn: Optional[Callable[[], Any]] = None,
        peer_file: Optional[str] = None,
        fleet_url: Optional[str] = None,
        migrate_timeout_s: float = 30.0,
    ):
        self.scheduler = scheduler
        self.host = host
        self.port = port  # rebound to the real port after bind (port=0 = ephemeral)
        # disaggregated fleet identity: replicas carry disjoint uid spaces so
        # a migrated request's donor uid (folded into its sampling keys, so
        # it must travel unchanged) can never collide with a local mint
        self.replica_id = os.environ.get("RELORA_TPU_REPLICA_ID", f"pid{os.getpid()}")
        uid_base = (
            (zlib.crc32(self.replica_id.encode()) % 1021 + 1) << 21
            if "RELORA_TPU_REPLICA_ID" in os.environ
            else 0
        )
        self.admission = AdmissionController(
            max_queue, retry_after_s=retry_after_s, uid_base=uid_base
        )
        self.stats = ServeMetrics()
        self.metrics = metrics
        if tracer is None:
            # per-process JSONL sink (pid-suffixed: supervisor fleets run N
            # replicas against one trace dir) so tools/trace_report.py can
            # merge replica spans with the router's under one request id
            trace_dir = os.environ.get("RELORA_TPU_TRACE_DIR")
            tracer = Tracer(
                service="serve",
                jsonl_path=(
                    os.path.join(trace_dir, f"serve_spans_{os.getpid()}.jsonl")
                    if trace_dir
                    else None
                ),
            )
        self.tracer = tracer
        # thread the server's tracer + registry into the scheduler so
        # prefill/insert/decode spans carry the same request trace ids and
        # the per-phase histograms land on this /metrics endpoint (a
        # scheduler built with its own tracer/registry keeps them)
        if isinstance(scheduler.tracer, NoopTracer):
            scheduler.tracer = self.tracer
        if scheduler.obs_registry is None:
            scheduler.obs_registry = self.stats
            scheduler.publish_constants()
        # multi-tenant: materialize the per-adapter series at zero so a
        # scrape taken before any tenant traffic still shows every adapter
        # the server can route to (absent-vs-zero is a real distinction for
        # dashboards doing rate() over counters)
        registry = getattr(scheduler, "adapter_registry", None)
        if registry is not None:
            if registry.metrics is None:
                registry.metrics = self.stats  # evictions counter + load histogram
            self.stats.inc("adapter_requests_total", ("adapter", "base"), 0)
            for name in registry.list_adapters():
                self.stats.inc("adapter_requests_total", ("adapter", name), 0)
            self.stats.inc("adapter_evictions_total", by=0)
            self.stats.set_gauge("adapter_slots_used", registry.slots_used())
            self.stats.materialize_histogram("adapter_load_seconds")
        # the collector's error_rate is derived from requests_finished_total
        # deltas; materialize the counter at zero so a replica that has not
        # finished a request yet still exports error_rate = 0.0 (absent
        # series would blind the SLO engine during warmup)
        self.stats.inc("requests_finished_total", ("reason", "stop"), 0)
        self.stats.inc("requests_finished_total", ("reason", "error"), 0)
        self.default_max_new_tokens = default_max_new_tokens
        self.default_temperature = default_temperature
        self.default_top_p = default_top_p
        self.started = threading.Event()  # set once the listener is bound
        self.drained = threading.Event()  # set once the model thread exits
        self._t_start = time.monotonic()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._shutdown: Optional[asyncio.Event] = None
        self._handler_tasks: Set[asyncio.Task] = set()
        self._active: Dict[int, Ticket] = {}  # model thread only
        self._worker = threading.Thread(
            target=self._model_loop, name="serve-model", daemon=True
        )
        self._worker_error: Optional[BaseException] = None
        # -- self-diagnosis ----------------------------------------------------
        # stall watchdog: no decode step completed for stall_timeout_s while
        # the scheduler had work -> healthz flips to 503 "stuck" + one flight
        # dump per episode (0 disables; set it above your worst cold compile)
        self.stall_timeout_s = stall_timeout_s
        # after the model thread dies, keep the listener up this long so
        # health probes observe the 503 "error" state (a router ejects on
        # status, not just connection-refused) before the process exits
        self.error_linger_s = error_linger_s
        # feeds faults.serve_tick; incremented from the model thread (local
        # decode) AND the event loop (migration-relay streams), so locked
        self._tokens_emitted = 0
        self._emitted_lock = threading.Lock()
        # -- in-place weight reload (continuous deployment) --------------------
        # reload_prepare(path) runs off the model thread (verify manifest +
        # restore to host memory) and returns the apply closure the model
        # thread honors at an idle decode boundary — the PreemptionGuard
        # "honor at the boundary" shape, with the decode round as boundary
        self.reload_prepare = reload_prepare
        self.weights_version = weights_version
        self.weights_checkpoint = weights_checkpoint
        self.stats.set_gauge("weights_version", weights_version)
        self._reload_lock = threading.Lock()
        self._pending_reload: Optional[_ReloadRequest] = None
        self._last_step_t = time.monotonic()
        self._model_busy = False  # model thread writes; watchdog reads
        # stream events posted inside the running scheduler step (None outside one)
        self._outbox: Optional[List[Tuple[Any, Any, Tuple[str, Any, Any]]]] = None
        self._stuck = False  # watchdog writes; healthz reads
        self._watchdog: Optional[threading.Thread] = None
        # -- router-aware warmup ----------------------------------------------
        # warmup_fn runs first on the model thread: the listener binds (and
        # the port file lands) immediately so the supervisor/collector see
        # the replica, but /healthz answers 503 "warming" until the compile
        # buckets are paid for — a health-probing router never sends live
        # traffic into a cold replica's compile stall.  Promotion to "ok" is
        # the warmup report completing; a warmup failure takes the normal
        # worker-error path instead.
        self.warmup_fn = warmup_fn
        self.warmup_report: Optional[Any] = None
        self._warming = warmup_fn is not None
        self.stats.set_gauge("warming", 1 if self._warming else 0)
        # -- disaggregated prefill/decode tier ---------------------------------
        # role comes from the scheduler (serve.py --role); peer_file is the
        # supervisor-maintained roster; fleet_url reaches the collector's
        # /fleet/prefix directory.  The inbox carries cross-thread work INTO
        # the model thread (handoff outcomes, migrated-run inserts, prefix
        # exports) — drained once per model-loop iteration, the same
        # idle-boundary discipline as _ReloadRequest.
        self.role = getattr(scheduler, "role", "mixed")
        self.peer_file = peer_file
        self.fleet_url = fleet_url
        self.migrate_timeout_s = migrate_timeout_s
        self._disagg_inbox: Deque[Tuple[str, Any]] = deque()
        if hasattr(scheduler, "migration_sink"):
            if self.role == "prefill" and peer_file:
                scheduler.migration_sink = self._migration_sink
            if fleet_url:
                scheduler.prefix_fetch = self._prefix_fetch
            # materialize the disagg counters at zero at startup (RTL703 +
            # the collector's *_per_s derivations need the series from the
            # very first scrape, not the first migration)
            for name in (
                "pages_migrated_total",
                "migration_bytes_total",
                "migration_failures_total",
                "migrated_inserts_total",
                "prefix_fetch_total",
                "prefix_fetch_failures_total",
            ):
                self.stats.inc(name, by=0)

    # -- lifecycle -----------------------------------------------------------

    def begin_drain(self) -> None:
        """Stop admitting (new requests get 503), finish in-flight and queued
        work, then shut down.  Thread-safe and idempotent."""
        if self.admission.draining:
            return
        logger.info("drain requested: rejecting new requests, finishing in-flight")
        self.admission.begin_drain()
        self.stats.set_gauge("draining", 1)
        if self.metrics is not None:
            self.metrics.event(
                "serve_drain_begin",
                queue_depth=self.admission.depth(),
                active_slots=self.scheduler.active_slots,
            )

    async def serve_forever(self, *, install_signal_handlers: bool = True) -> None:
        self._loop = asyncio.get_running_loop()
        self._shutdown = asyncio.Event()
        server = await asyncio.start_server(self._client_connected, self.host, self.port)
        self.port = server.sockets[0].getsockname()[1]
        if install_signal_handlers:
            try:
                self._loop.add_signal_handler(signal.SIGTERM, self.begin_drain)
            except (NotImplementedError, RuntimeError):
                # non-main thread or non-Unix loop: callers drain explicitly
                logger.warning("SIGTERM handler unavailable; use begin_drain()")
        self.stats.set_gauge("draining", 0)
        self._worker.start()
        if self.stall_timeout_s > 0:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="serve-watchdog", daemon=True
            )
            self._watchdog.start()
        self.started.set()
        logger.info(f"serving on http://{self.host}:{self.port}")
        async with server:
            await self._shutdown.wait()
            server.close()
            await server.wait_closed()
        if self._handler_tasks:
            # finish events are already queued on the loop; give handlers a
            # bounded grace to flush their final bytes
            await asyncio.wait(set(self._handler_tasks), timeout=10.0)
        if self.metrics is not None:
            self.metrics.event("serve_drain_complete", **self.stats.snapshot())
        logger.info("drain complete; server stopped")
        if self._worker_error is not None:
            raise RuntimeError("model thread died") from self._worker_error

    def _signal_shutdown(self) -> None:
        loop, shutdown = self._loop, self._shutdown
        if loop is None or shutdown is None:
            return
        try:
            loop.call_soon_threadsafe(shutdown.set)
        except RuntimeError:
            pass  # loop already closed

    # -- model thread --------------------------------------------------------

    def _post(self, loop, events: "asyncio.Queue", item: Tuple[str, Any, Any]) -> None:
        """Hand a stream event (a token, a finish) to the request's handler on
        the event loop.  Inside a scheduler step the model thread only notes
        it: a round's events — a token a decoding row — go over in one
        ``call_soon_threadsafe`` when the step ends (:meth:`_flush_outbox`).
        One wake-up a row made the loop thread take the GIL row by row, so
        that the step's commit waited for every stream's write (and, in a
        process that also holds the clients, for every client's read) while
        the device stood idle: 17 ms of a 42 ms round at 64 rows (PERF.md
        section 6, PR 34)."""
        outbox = self._outbox
        if outbox is not None and threading.current_thread() is self._worker:
            outbox.append((loop, events, item))
            return
        try:
            loop.call_soon_threadsafe(events.put_nowait, item)
        except RuntimeError:
            pass  # loop closed mid-drain; the record still lands in metrics

    def _flush_outbox(self) -> None:
        outbox, self._outbox = self._outbox, None
        if not outbox:
            return

        def deliver(batch) -> None:
            for _, events, item in batch:
                events.put_nowait(item)

        try:
            outbox[0][0].call_soon_threadsafe(deliver, outbox)
        except RuntimeError:
            pass  # loop closed mid-drain

    def _model_loop(self) -> None:
        """The scheduler's single driving thread: claim tickets while slots
        are free, apply cancellations, run one decode round, repeat.  Exits
        when draining and nothing is left anywhere."""
        sched = self.scheduler
        try:
            if self.warmup_fn is not None:
                t0 = time.monotonic()
                logger.info("warmup: paying compile buckets before going routable")
                self.warmup_report = self.warmup_fn()
                self._warming = False
                self.stats.set_gauge("warming", 0)
                self._last_step_t = time.monotonic()
                logger.info(
                    f"warmup complete in {time.monotonic() - t0:.1f}s; healthz -> ok"
                )
                if self.metrics is not None:
                    detail = (
                        self.warmup_report
                        if isinstance(self.warmup_report, dict)
                        else {}
                    )
                    self.metrics.event(
                        "serve_warm", duration_s=round(time.monotonic() - t0, 3),
                        **detail,
                    )
            while True:
                faults.serve_tick(self._tokens_emitted)  # serving drills only
                # the loop's own part of the scheduler's host gap, between two
                # rounds: the device waits through it, so it has a name
                with self.tracer.span("claim") as sp_claim:
                    # a pending reload pauses *claiming* only: queued tickets
                    # wait in admission (nothing is dropped), in-flight
                    # requests finish entirely on the old weights (per-request
                    # version purity), and the swap happens at the idle
                    # boundary below
                    reload_req = self._pending_reload
                    claimed = 0
                    while reload_req is None and (
                        sched.active_slots + sched.queue_depth < sched.max_batch
                    ):
                        ticket = self.admission.pop(timeout=None)
                        if ticket is None:
                            break
                        self._claim(ticket)
                        claimed += 1
                    for uid, ticket in list(self._active.items()):
                        if ticket.cancelled.is_set():
                            sched.cancel(uid)  # fires on_finish -> _active cleanup
                    self._drain_disagg_inbox()
                    self.stats.set_gauge(
                        "queue_depth", self.admission.depth() + sched.queue_depth
                    )
                    self.stats.set_gauge("active_slots", sched.active_slots)
                    self.stats.set_gauge(
                        "retry_after_s", round(self.admission.retry_after_s, 3)
                    )
                    busy = sched.has_work()
                    sp_claim.set(claimed=claimed)
                    if not claimed and not busy:
                        sp_claim.drop()  # an idle turn leaves nothing
                if busy:
                    self._model_busy = True
                    self._outbox = []  # what the step's callbacks post rides out together
                    try:
                        sched.step()
                    finally:
                        with self.tracer.span("flush_outbox"):
                            self._flush_outbox()
                    self._last_step_t = time.monotonic()
                    continue
                self._model_busy = False
                sched.drop_host_gap()  # waiting for a request is not the host's gap
                self._last_step_t = time.monotonic()  # idle is not a stall
                if reload_req is not None:
                    # the boundary: no active slots, no scheduler queue — swap
                    # weights now, then resume claiming on the next iteration
                    self._apply_reload(reload_req)
                    continue
                if self.admission.draining and self.admission.depth() == 0:
                    break
                ticket = self.admission.pop(timeout=_IDLE_POP_S)
                if ticket is not None:
                    with self.tracer.span("claim", claimed=1):
                        self._claim(ticket)
        except BaseException as e:
            self._worker_error = e
            logger.error(f"model thread died: {e!r}")
            self._fail_pending(e)
        finally:
            self._fail_reload("model thread exited")
            self.drained.set()
            if self._worker_error is not None and self.error_linger_s > 0:
                time.sleep(self.error_linger_s)
            self._signal_shutdown()

    def _fail_pending(self, error: BaseException) -> None:
        """Model-thread death: terminally complete every active and queued
        request with ``finish_reason="error"`` instead of stranding its
        stream until the client gives up.  Host-side bookkeeping only — safe
        even when the jitted step itself is what blew up."""
        detail = f"model thread died: {error!r}"
        self.stats.set_gauge("model_dead", 1)
        try:
            # requests the scheduler owns (decoding or scheduler-queued):
            # fail_all fires the normal on_finish wrappers, so metrics, spans
            # and the SSE finish events all flow through the standard path
            self.scheduler.fail_all(reason="error", detail=detail)
        except Exception as e:
            logger.error(f"fail_all after model-thread death failed too: {e!r}")
            for _uid, ticket in list(self._active.items()):
                self._active.pop(_uid, None)
                try:
                    ticket.on_finish(
                        Completion(
                            uid=ticket.uid,
                            tokens=[],
                            finish_reason="error",
                            prompt_tokens=len(ticket.request.prompt),
                            ttft_s=0.0,
                            latency_s=0.0,
                            error=detail,
                        )
                    )
                except Exception:
                    pass
        # tickets still waiting in the admission queue, never claimed
        while True:
            ticket = self.admission.pop(timeout=None)
            if ticket is None:
                break
            self.stats.inc("requests_finished_total", ("reason", "error"))
            if ticket.queue_span is not None:
                ticket.queue_span.set(outcome="error").end()
            if ticket.span is not None:
                ticket.span.set(finish_reason="error", output_tokens=0).end()
            try:
                ticket.on_finish(
                    Completion(
                        uid=ticket.uid,
                        tokens=[],
                        finish_reason="error",
                        prompt_tokens=len(ticket.request.prompt),
                        ttft_s=0.0,
                        latency_s=0.0,
                        error=detail,
                    )
                )
            except Exception as e:
                logger.warning(f"request {ticket.uid}: finish callback failed: {e!r}")

    # -- in-place weight reload ----------------------------------------------

    def request_reload(self, apply: Callable[[], None], version: int, checkpoint: str) -> _ReloadRequest:
        """Queue a prepared weight swap for the model thread's next idle
        boundary.  Thread-safe; raises RuntimeError while another reload is
        still pending (one swap at a time keeps versions totally ordered)."""
        req = _ReloadRequest(apply, version, checkpoint)
        with self._reload_lock:
            if self._pending_reload is not None:
                raise RuntimeError("a weight reload is already pending")
            self._pending_reload = req
        return req

    def _apply_reload(self, req: _ReloadRequest) -> None:
        """Model thread, idle boundary: run the prepared swap.  Any failure
        fails closed — the old weights keep serving, the version does not
        move, and the error is reported to the requester."""
        try:
            faults.maybe_fail("deploy_reload")
            req.apply()
        except Exception as e:
            req.error = f"{e!r}"
            self.stats.inc("weights_reload_failures_total")
            logger.error(
                f"weight reload to {req.checkpoint!r} failed ({e!r}); "
                f"keeping weights_version {self.weights_version}"
            )
            if self.metrics is not None:
                self.metrics.event(
                    "serve_reload_failed", checkpoint=req.checkpoint, error=f"{e!r}"
                )
        else:
            req.ok = True
            self.weights_version = req.version
            self.weights_checkpoint = req.checkpoint
            self.stats.inc("weights_reloads_total")
            self.stats.set_gauge("weights_version", req.version)
            self.scheduler.publish_constants()
            logger.info(
                f"weights hot-swapped to version {req.version} ({req.checkpoint})"
            )
            if self.metrics is not None:
                self.metrics.event(
                    "serve_reload", weights_version=req.version, checkpoint=req.checkpoint
                )
        finally:
            with self._reload_lock:
                self._pending_reload = None
            req.done.set()

    def _fail_reload(self, detail: str) -> None:
        """Complete a still-pending reload with an error so its requester
        never hangs (model-thread death or drain exit)."""
        with self._reload_lock:
            req, self._pending_reload = self._pending_reload, None
        if req is not None and not req.done.is_set():
            req.error = detail
            self.stats.inc("weights_reload_failures_total")
            req.done.set()

    # -- stall watchdog ------------------------------------------------------

    def _watchdog_loop(self) -> None:
        """Decode-progress watchdog: when the scheduler had work but no step
        completed for ``stall_timeout_s`` (wedged device call, injected
        ``serve_stall``, runaway compile), flip ``/healthz`` to 503 "stuck"
        so the router ejects this replica, and dump the flight recorder once
        per episode for offline triage.  Un-sticks by itself when a step
        completes — a recovered replica goes back into rotation."""
        interval = max(0.02, min(self.stall_timeout_s / 4.0, 1.0))
        while not self.drained.is_set():
            time.sleep(interval)
            # _model_busy/_last_step_t freeze at their last values while the
            # model thread is wedged — which is exactly the signal
            stalled = (
                self._model_busy
                and time.monotonic() - self._last_step_t > self.stall_timeout_s
            )
            if stalled and not self._stuck:
                self._stuck = True
                self.stats.set_gauge("stuck", 1)
                logger.error(
                    f"watchdog: no decode step for {self.stall_timeout_s:.1f}s "
                    "with work queued; healthz -> 503 stuck"
                )
                dump_on_fault("serve_stall")
                if self.metrics is not None:
                    self.metrics.event(
                        "serve_stall_detected",
                        stall_timeout_s=self.stall_timeout_s,
                        active_slots=self.scheduler.active_slots,
                    )
            elif not stalled and self._stuck:
                self._stuck = False
                self.stats.set_gauge("stuck", 0)
                logger.warning("watchdog: decode progress resumed; healthz -> ok")

    def _claim(self, ticket: Ticket) -> None:
        """Hand one admitted ticket to the scheduler (model thread only)."""
        # the queue-wait span opened at admission ends here, where the model
        # thread claims the ticket (cross-thread: started on the event loop)
        if ticket.queue_span is not None:
            self.stats.observe("queue_wait_seconds", ticket.queue_span.end())
        if ticket.cancelled.is_set():
            # client left while the request was still queued: never admit it
            self.stats.inc("requests_finished_total", ("reason", "cancelled"))
            if ticket.span is not None:
                ticket.span.set(finish_reason="cancelled", output_tokens=0).end()
            ticket.on_finish(
                Completion(
                    uid=ticket.uid,
                    tokens=[],
                    finish_reason="cancelled",
                    prompt_tokens=len(ticket.request.prompt),
                    ttft_s=0.0,
                    latency_s=0.0,
                )
            )
            return
        self._active[ticket.uid] = ticket
        self.scheduler.submit(
            ticket.request,
            on_token=lambda uid, tok, idx, _t=ticket: self._token_cb(_t, uid, tok, idx),
            on_finish=lambda completion, _t=ticket: self._finish_cb(_t, completion),
            deadline=ticket.deadline,
            trace_id=ticket.trace_id,
        )

    def _token_cb(self, ticket: Ticket, uid: int, token: int, index: int) -> None:
        """Per-token bookkeeping shared by local decode and relayed migration
        streams: latency histograms, the Retry-After TPOT estimate, and the
        client's own on_token."""
        now = time.monotonic()
        if index == 0:
            self.stats.observe("ttft_seconds", now - ticket.t_enqueue)
        elif ticket.t_last_token is not None:
            tpot = now - ticket.t_last_token
            self.stats.observe("tpot_seconds", tpot)
            self.admission.note_tpot(tpot)  # feeds the Retry-After hint
        ticket.t_last_token = now
        with self._emitted_lock:
            self._tokens_emitted += 1
        self.stats.inc("tokens_generated_total")
        ticket.on_token(uid, token, index)

    def _finish_cb(self, ticket: Ticket, completion: Completion) -> None:
        """Finish bookkeeping shared by local decode and relayed migration
        streams: counters, e2e latency, the root span, the client stream."""
        self._active.pop(completion.uid, None)
        self.stats.inc(
            "requests_finished_total", ("reason", completion.finish_reason)
        )
        self.stats.observe("e2e_latency_seconds", time.monotonic() - ticket.t_enqueue)
        if ticket.span is not None:
            ticket.span.set(
                finish_reason=completion.finish_reason,
                output_tokens=len(completion.tokens),
            ).end()
        ticket.on_finish(completion)

    # -- disaggregated handoff / fleet prefix fetch --------------------------
    #
    # Thread contract: the scheduler is model-thread-only, so every disagg
    # mutation (handoff outcome, migrated-run insert, prefix export) crosses
    # from the event loop through _disagg_inbox and is applied by
    # _drain_disagg_inbox inside the model loop.  The donor-side relay
    # (_migrate_task) and the internal HTTP handlers live on the event loop;
    # _migration_sink and _prefix_fetch are called *by* the scheduler on the
    # model thread.

    def _drain_disagg_inbox(self) -> None:
        """Model thread: apply queued cross-thread disagg work."""
        sched = self.scheduler
        while self._disagg_inbox:
            kind, payload = self._disagg_inbox.popleft()
            try:
                if kind == "failed":
                    sched.migration_failed(payload[0], payload[1])
                elif kind == "commit":
                    sched.migration_commit(payload[0], bytes_sent=payload[1])
                elif kind == "abort":
                    sched.migration_abort(payload[0], payload[1])
                elif kind == "insert":
                    self._apply_migrate_insert(*payload)
                elif kind == "export_prefix":
                    self._apply_prefix_export(*payload)
            except Exception as e:
                # inbox work must never kill the model thread; each message
                # has its own fail-open story and this is the last resort
                logger.warning(f"disagg inbox {kind!r} failed: {e!r}")

    def _apply_migrate_insert(
        self,
        record: Dict[str, Any],
        arrays: Any,
        ticket: Ticket,
        done: threading.Event,
        result: Dict[str, Any],
    ) -> None:
        """Model thread: adopt a migrated page run into a decode slot.  Any
        raise lands in ``result["error"]`` and the donor fails open."""
        try:
            if ticket.cancelled.is_set():
                raise RuntimeError("donor went away before the insert")
            self.scheduler.submit_migrated(
                record,
                arrays,
                on_token=lambda uid, tok, idx, _t=ticket: self._token_cb(
                    _t, uid, tok, idx
                ),
                on_finish=lambda completion, _t=ticket: self._finish_cb(
                    _t, completion
                ),
                deadline=ticket.deadline,
                trace_id=ticket.trace_id,
            )
            self._active[ticket.uid] = ticket
        except Exception as e:
            result["error"] = str(e)
        finally:
            done.set()

    def _apply_prefix_export(
        self, digest_hex: str, done: threading.Event, result: Dict[str, Any]
    ) -> None:
        """Model thread: pin + export a locally cached prefix run for a peer
        (GET /internal/prefix/<digest>).  ``result["blob"]`` stays absent on
        a miss — the handler answers 404 and the peer falls open.  The
        acquire/decref pair is the donor-side pin: LRU eviction cannot free
        the run while export_page_run is copying it off the device."""
        try:
            sched = self.scheduler
            cache = getattr(sched, "prefix_cache", None)
            if cache is None:
                return
            acquired = cache.acquire(digest_hex)
            if acquired is None:
                return
            pages, n_tokens = acquired
            try:
                entries = sched.engine.export_page_run(sched._ensure_pool(), pages)
            finally:
                sched.allocator.decref(pages)  # release the transfer pin
            result["blob"] = _encode_page_run(
                {
                    "digest": digest_hex,
                    "n_tokens": n_tokens,
                    "n_pages": len(pages),
                },
                entries,
            )
        except Exception as e:
            result["error"] = str(e)
        finally:
            done.set()

    def _migration_sink(self, record: Dict[str, Any], entries: Any) -> bool:
        """Model thread (scheduler._maybe_migrate): pick decode peers, frame
        the run, and launch the async handoff.  Returning False means the
        handoff could not even start — the scheduler fails open on the spot."""
        loop = self._loop
        if loop is None or loop.is_closed():
            return False
        ticket = self._active.get(int(record["uid"]))
        if ticket is None or ticket.cancelled.is_set():
            return False
        peers = disagg.load_peers(self.peer_file)
        candidates = disagg.pick_peers(
            peers, role="decode", exclude_rid=self.replica_id
        )
        if not candidates:
            return False
        # enrich with what only the server knows: the remaining deadline and
        # the request id, so the peer's deadline/spans behave like a direct hit
        if ticket.deadline is not None:
            record["deadline_s"] = max(0.1, ticket.deadline - time.monotonic())
        if ticket.trace_id:
            record["trace_id"] = ticket.trace_id
        try:
            blob = _encode_page_run(record, entries)
        except Exception as e:
            logger.warning(f"request {record['uid']}: wire encode failed: {e!r}")
            return False
        asyncio.run_coroutine_threadsafe(
            self._migrate_task(record, blob, ticket, candidates[:2]), loop
        )
        return True

    async def _migrate_task(
        self, record: Dict[str, Any], blob: bytes, ticket: Ticket, candidates: list
    ) -> None:
        """Event loop: drive the handoff against each candidate peer.  Per
        attempt: "relayed" (peer finished the stream — commit the donor
        slot), "rejected" (no token reached the client — the next peer, or
        fail open to local decode, is still token-identical), "aborted"
        (peer died after relaying a token — the PR 9 idempotency boundary
        forbids a silent replay, so the client gets a typed error finish)."""
        uid = int(record["uid"])
        detail = "no decode peer accepted the handoff"
        for peer in candidates:
            try:
                outcome, detail = await self._migrate_attempt(
                    record, blob, ticket, peer
                )
            except Exception as e:
                outcome, detail = "rejected", f"{peer.get('rid')}: {e!r}"
            if outcome == "relayed":
                self._disagg_inbox.append(("commit", (uid, len(blob))))
                return
            if outcome == "aborted":
                self._disagg_inbox.append(("abort", (uid, detail)))
                try:
                    self._finish_cb(
                        ticket,
                        Completion(
                            uid=uid,
                            tokens=[],
                            finish_reason="error",
                            prompt_tokens=len(ticket.request.prompt),
                            ttft_s=0.0,
                            latency_s=time.monotonic() - ticket.t_enqueue,
                            error=f"migration_failed: {detail}",
                        ),
                    )
                except Exception:
                    pass
                if self.metrics is not None:
                    self.metrics.event(
                        "migration_failed", uid=uid, detail=str(detail), aborted=True
                    )
                return
            logger.warning(
                f"request {uid}: handoff to {peer.get('rid')} rejected ({detail})"
            )
        self._disagg_inbox.append(("failed", (uid, detail)))
        if self.metrics is not None:
            self.metrics.event("migration_failed", uid=uid, detail=str(detail))

    async def _migrate_attempt(
        self, record: Dict[str, Any], blob: bytes, ticket: Ticket, peer: Dict[str, Any]
    ) -> Tuple[str, str]:
        """One POST /internal/migrate exchange: ship the framed run, then
        relay the peer's SSE continuation into the client's ticket callbacks.
        Returns ("relayed" | "rejected" | "aborted", detail)."""
        host = str(peer.get("host") or "127.0.0.1")
        port = int(peer["port"])
        uid = int(record["uid"])
        relayed_any = False
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(host, port), timeout=5.0
            )
        except (OSError, asyncio.TimeoutError) as e:
            return "rejected", f"connect {host}:{port}: {e!r}"
        try:
            writer.write(
                (
                    f"POST /internal/migrate HTTP/1.1\r\n"
                    f"Host: {host}:{port}\r\n"
                    f"Content-Type: application/octet-stream\r\n"
                    f"Content-Length: {len(blob)}\r\n"
                    f"Connection: close\r\n\r\n"
                ).encode()
            )
            writer.write(blob)
            await asyncio.wait_for(writer.drain(), timeout=self.migrate_timeout_s)
            status_line = await asyncio.wait_for(
                reader.readline(), timeout=self.migrate_timeout_s
            )
            parts = status_line.decode("latin-1", "replace").split()
            status = int(parts[1]) if len(parts) >= 2 and parts[1].isdigit() else 0
            while True:  # response headers; SSE or JSON body follows
                line = await asyncio.wait_for(
                    reader.readline(), timeout=self.migrate_timeout_s
                )
                if line in (b"\r\n", b"\n", b""):
                    break
            if status != 200:
                body = await reader.read(4096)
                return "rejected", f"{host}:{port} -> {status} {body[:200]!r}"
            while True:
                if ticket.cancelled.is_set():
                    # client left: abandon the relay (closing our end is the
                    # peer's disconnect signal — it cancels and frees pages),
                    # count the cancel, and commit the donor slot away
                    self._finish_cb(
                        ticket,
                        Completion(
                            uid=uid,
                            tokens=[],
                            finish_reason="cancelled",
                            prompt_tokens=len(ticket.request.prompt),
                            ttft_s=0.0,
                            latency_s=time.monotonic() - ticket.t_enqueue,
                        ),
                    )
                    return "relayed", "client cancelled mid-relay"
                line = await asyncio.wait_for(
                    reader.readline(), timeout=self.migrate_timeout_s
                )
                if not line:
                    if relayed_any:
                        return "aborted", f"{host}:{port}: peer died mid-stream"
                    return "rejected", f"{host}:{port}: peer died before first token"
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[len(b"data: ") :]
                if data == b"[DONE]":
                    continue  # finish record already handled below
                try:
                    rec = json.loads(data.decode("utf-8"))
                except (UnicodeDecodeError, json.JSONDecodeError):
                    continue
                if not isinstance(rec, dict):
                    continue
                if "finish_reason" in rec:
                    if rec["finish_reason"] == "error" and not relayed_any:
                        # peer failed before anything reached the client:
                        # safe to try the next peer / fail open locally
                        return "rejected", f"{host}:{port}: {rec.get('error')}"
                    self._finish_cb(
                        ticket,
                        Completion(
                            uid=uid,
                            tokens=[int(t) for t in rec.get("tokens", [])],
                            finish_reason=str(rec["finish_reason"]),
                            prompt_tokens=int(
                                rec.get("prompt_tokens", len(ticket.request.prompt))
                            ),
                            ttft_s=float(rec.get("ttft_s", 0.0)),
                            latency_s=time.monotonic() - ticket.t_enqueue,
                            error=rec.get("error"),
                        ),
                    )
                    return "relayed", "ok"
                if "token" in rec:
                    relayed_any = True
                    self._token_cb(ticket, uid, int(rec["token"]), int(rec["index"]))
        except (asyncio.TimeoutError, ConnectionError, OSError) as e:
            if relayed_any:
                return "aborted", f"{host}:{port}: {e!r}"
            return "rejected", f"{host}:{port}: {e!r}"
        finally:
            try:
                writer.close()
            except Exception:
                pass

    def _prefix_fetch(self, digests: list) -> Optional[Tuple[int, Any, int]]:
        """Model thread (scheduler._fetch_prefix): resolve the longest known
        prefix digest via the fleet directory, then pull the run from the
        holder's /internal/prefix endpoint.  Returns ``(n_tokens, entries,
        nbytes)`` or None; raises propagate into the scheduler's fail-open
        accounting (prefix_fetch_failures_total)."""
        url = self.fleet_url
        if not url:
            return None
        if os.path.exists(url):
            # the supervisor hands replicas a router-port *file* (the router
            # binds an ephemeral port after the replicas spawn)
            try:
                with open(url) as f:
                    url = f.read().strip()
                if ":" not in url:
                    url = f"127.0.0.1:{int(url)}"
            except (OSError, ValueError):
                return None
        parts = urlsplit(url if "//" in url else f"//{url}")
        status, body = disagg.http_fetch(
            parts.hostname or "127.0.0.1",
            parts.port or 80,
            "/fleet/prefix?d=" + ",".join(digests) + "&exclude=" + self.replica_id,
            timeout_s=2.0,
        )
        if status != 200:
            return None
        doc = json.loads(body.decode("utf-8"))
        digest = doc.get("digest")
        if not digest or doc.get("replica") == self.replica_id:
            return None
        status, blob = disagg.http_fetch(
            str(doc["host"]),
            int(doc["port"]),
            f"/internal/prefix/{digest}",
            timeout_s=5.0,
        )
        if status != 200:
            return None  # stale directory entry: the holder evicted the run
        meta, arrays = _decode_page_run(blob)
        return int(meta["n_tokens"]), arrays, len(blob)

    # -- asyncio handlers ----------------------------------------------------

    async def _client_connected(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        task = asyncio.current_task()
        if task is not None:
            self._handler_tasks.add(task)
            task.add_done_callback(self._handler_tasks.discard)
        try:
            await self._handle(reader, writer)
        except (ConnectionError, asyncio.IncompleteReadError, TimeoutError):
            pass  # client went away; per-request cleanup already ran
        except Exception as e:
            logger.warning(f"handler error: {e!r}")
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    async def _handle(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        if faults.should("serve_accept_drop"):
            # drill: an accepted connection that dies before a byte of
            # response — the shape a router's pre-stream retry must absorb
            self.stats.inc("accept_drops_total")
            return
        try:
            parsed = await asyncio.wait_for(_read_http_request(reader), _REQUEST_TIMEOUT_S)
        except ValueError as e:
            await _respond_json(writer, 400, {"error": str(e)})
            return
        if parsed is None:
            return
        method, path, headers, body = parsed
        route = path.split("?", 1)[0]
        if route == "/healthz" and method == "GET":
            self.stats.inc("http_requests_total", ("route", "healthz"))
            await self._handle_healthz(writer)
        elif route == "/metrics" and method == "GET":
            self.stats.inc("http_requests_total", ("route", "metrics"))
            await _respond(writer, 200, self.stats.render(), content_type="text/plain; version=0.0.4")
        elif route == "/v1/generate":
            self.stats.inc("http_requests_total", ("route", "generate"))
            if method != "POST":
                await _respond_json(writer, 405, {"error": "use POST"})
                return
            await self._handle_generate(reader, writer, body, headers)
        elif route == "/admin/reload":
            self.stats.inc("http_requests_total", ("route", "reload"))
            if method != "POST":
                await _respond_json(writer, 405, {"error": "use POST"})
                return
            await self._handle_reload(writer, body)
        elif route == "/internal/migrate":
            self.stats.inc("http_requests_total", ("route", "migrate"))
            if method != "POST":
                await _respond_json(writer, 405, {"error": "use POST"})
                return
            await self._handle_migrate(reader, writer, body)
        elif route.startswith("/internal/prefix/"):
            self.stats.inc("http_requests_total", ("route", "prefix"))
            if method != "GET":
                await _respond_json(writer, 405, {"error": "use GET"})
                return
            await self._handle_prefix(writer, route[len("/internal/prefix/") :])
        else:
            self.stats.inc("http_requests_total", ("route", "other"))
            await _respond_json(writer, 404, {"error": f"no route {route}"})

    async def _handle_healthz(self, writer: asyncio.StreamWriter) -> None:
        # precedence: a dead worker trumps everything, a wedged worker trumps
        # drain state, drain trumps warming — the router must stop routing
        # (or never start, for "warming") on all four
        if self._worker_error is not None:
            state, status = "error", 503
        elif self._stuck:
            state, status = "stuck", 503
        elif self.admission.draining:
            state, status = "draining", 503
        elif self._warming:
            state, status = "warming", 503
        else:
            state, status = "ok", 200
        payload = {
            "status": state,
            "active_slots": self.scheduler.active_slots,
            "queue_depth": self.admission.depth() + self.scheduler.queue_depth,
            "max_batch": self.scheduler.max_batch,
            "max_queue": self.admission.max_queue,
            "retry_after_s": round(self.admission.retry_after_s, 3),
            "uptime_s": round(time.monotonic() - self._t_start, 3),
            # numeric, so the fleet collector ingests it as a free
            # healthz_weights_version series per replica; the checkpoint path
            # is what a rolling updater reads back for its rollback target
            "weights_version": self.weights_version,
            "weights_checkpoint": self.weights_checkpoint,
            # disaggregated tier: the router reads role for pool routing; the
            # collector feeds the fleet prefix-page directory from the digest
            # list (both skipped by its numeric-only metrics ingestion)
            "role": self.role,
        }
        prefix_cache = getattr(self.scheduler, "prefix_cache", None)
        if prefix_cache is not None:
            try:
                payload["prefix_digests"] = prefix_cache.digests()
            except RuntimeError:
                pass  # model thread mutated the cache mid-iteration; next probe
        if self._worker_error is not None:
            payload["detail"] = f"model thread died: {self._worker_error!r}"
        elif self._stuck:
            payload["detail"] = (
                f"no decode step completed for {self.stall_timeout_s:.1f}s"
            )
        elif self._warming:
            payload["detail"] = "compile warmup in progress"
        # paged scheduler: pool pressure for the allocator-exhaustion triage
        # flow (docs/operations.md) — queued-but-healthy vs queued-and-starved
        paging_stats = getattr(self.scheduler, "paging_stats", None)
        if paging_stats is not None:
            payload["paging"] = paging_stats()
        # multi-tenant scheduler: slot occupancy + residency for the
        # adapter-slot-thrash triage flow (docs/operations.md)
        adapter_stats = getattr(self.scheduler, "adapter_stats", None)
        if adapter_stats is not None:
            stats = adapter_stats()
            if stats is not None:
                payload["adapters"] = stats
        await _respond_json(writer, status, payload)

    async def _handle_reload(self, writer: asyncio.StreamWriter, body: bytes) -> None:
        """POST /admin/reload {"checkpoint": path}: verify + restore the
        checkpoint off the model thread, then hand the swap to the model
        thread's idle boundary and wait for its verdict.  Every failure mode
        (no reload path, bad body, verify/restore error, swap error) leaves
        the old weights serving — the endpoint can only move the version
        forward on full success."""
        if self.reload_prepare is None:
            await _respond_json(
                writer, 501,
                {"error": "no reload path configured (start with a --checkpoint)"},
            )
            return
        if self._worker_error is not None:
            await _respond_json(
                writer, 503, {"error": f"model thread died: {self._worker_error!r}"}
            )
            return
        try:
            payload = json.loads(body.decode("utf-8") or "{}")
            path = payload.get("checkpoint")
            if not isinstance(path, str) or not path.strip():
                raise BadRequest('"checkpoint" must be a non-empty path string')
        except (UnicodeDecodeError, json.JSONDecodeError, BadRequest) as e:
            await _respond_json(writer, 400, {"error": str(e)})
            return
        path = path.strip()
        from relora_tpu.serve.deploy import checkpoint_step

        version = checkpoint_step(path)
        if version is None:
            version = self.weights_version + 1  # non-model_N dirs still order
        loop = asyncio.get_running_loop()
        try:
            # verify manifest + restore to host memory off the event loop AND
            # off the model thread — decode keeps running while this works
            apply = await loop.run_in_executor(None, self.reload_prepare, path)
        except Exception as e:
            self.stats.inc("weights_reload_failures_total")
            logger.error(f"reload rejected before any device write: {e!r}")
            if self.metrics is not None:
                self.metrics.event("serve_reload_failed", checkpoint=path, error=f"{e!r}")
            await _respond_json(
                writer, 422,
                {"error": f"{e}", "weights_version": self.weights_version},
            )
            return
        try:
            req = self.request_reload(apply, version, path)
        except RuntimeError as e:
            await _respond_json(
                writer, 409, {"error": str(e), "weights_version": self.weights_version}
            )
            return
        await loop.run_in_executor(None, req.done.wait)
        await _respond_json(
            writer,
            200 if req.ok else 500,
            {
                "ok": req.ok,
                "weights_version": self.weights_version,
                "weights_checkpoint": self.weights_checkpoint,
                **({"error": req.error} if req.error else {}),
            },
        )

    async def _handle_migrate(
        self,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        body: bytes,
    ) -> None:
        """POST /internal/migrate — adopt a donor's finished page run into a
        decode slot and stream the continuation back as SSE (the donor
        relays it to the real client).  Every rejection is a non-200 the
        donor maps to fail-open local decode, so rejecting here is always
        safe; accepting means this replica now owns the request's stream."""
        if self._worker_error is not None or self._warming or self.admission.draining:
            await _respond_json(writer, 503, {"error": "replica not accepting handoffs"})
            return
        try:
            record, arrays = _decode_page_run(body)
            if not isinstance(record, dict):
                raise ValueError("page-run meta must be an object")
            req = Request(
                uid=int(record["uid"]),
                prompt=[int(t) for t in record["prompt"]],
                max_new_tokens=int(record["max_new_tokens"]),
                temperature=float(record.get("temperature", 0.0)),
                top_p=float(record.get("top_p", 1.0)),
                spec=bool(record.get("spec", True)),
                adapter=record.get("adapter"),
            )
        except (ValueError, KeyError, TypeError) as e:
            await _respond_json(writer, 400, {"error": f"bad page run: {e}"})
            return
        loop = asyncio.get_running_loop()
        events: "asyncio.Queue[Tuple[str, Any, Any]]" = asyncio.Queue()

        def post(kind: str, a: Any = None, b: Any = None) -> None:
            self._post(loop, events, (kind, a, b))

        deadline_s = record.get("deadline_s")
        ticket = Ticket(
            uid=req.uid,
            request=req,
            deadline=(
                time.monotonic() + float(deadline_s)
                if isinstance(deadline_s, (int, float)) and deadline_s > 0
                else None
            ),
            on_token=lambda uid, tok, idx: post("token", tok, idx),
            on_finish=lambda completion: post("finish", completion),
            trace_id=record.get("trace_id"),
        )
        done = threading.Event()
        result: Dict[str, Any] = {}
        self._disagg_inbox.append(("insert", (record, arrays, ticket, done, result)))
        ok = await loop.run_in_executor(None, done.wait, self.migrate_timeout_s)
        if not ok:
            # flag the ticket so a late insert is rejected (or, if it already
            # landed, the cancel scan frees the slot) — never decode blind
            ticket.cancelled.set()
            await _respond_json(writer, 503, {"error": "migrated insert timed out"})
            return
        if result.get("error"):
            await _respond_json(writer, 409, {"error": result["error"]})
            return
        await self._stream_response(reader, writer, ticket, events)

    async def _handle_prefix(self, writer: asyncio.StreamWriter, digest_hex: str) -> None:
        """GET /internal/prefix/<digest> — export a pinned prefix page run
        for a peer.  404 on a miss (stale directory entry): the requester
        falls open to local prefill."""
        if self._worker_error is not None or self._warming:
            await _respond_json(writer, 503, {"error": "replica not serving prefixes"})
            return
        done = threading.Event()
        result: Dict[str, Any] = {}
        self._disagg_inbox.append(
            ("export_prefix", (digest_hex.strip(), done, result))
        )
        loop = asyncio.get_running_loop()
        ok = await loop.run_in_executor(None, done.wait, 10.0)
        blob = result.get("blob") if ok else None
        if blob is None:
            await _respond_json(
                writer,
                404,
                {"error": result.get("error") or "prefix not cached on this replica"},
            )
            return
        writer.write(
            _head(200, "OK", "application/octet-stream", content_length=len(blob))
        )
        writer.write(blob)
        await writer.drain()

    async def _handle_generate(
        self,
        reader: asyncio.StreamReader,
        writer: asyncio.StreamWriter,
        body: bytes,
        headers: Optional[Dict[str, str]] = None,
    ) -> None:
        # the request id is the span trace id AND the X-Request-Id response
        # header: a caller-supplied header is honored (so a gateway's id
        # threads through every phase span), otherwise one is minted here
        rid = ((headers or {}).get("x-request-id") or "").strip() or new_trace_id()
        rid_header = {"X-Request-Id": rid}
        if self._worker_error is not None:
            # dead worker, listener lingering for health probes: fail fast
            # instead of queueing a ticket nothing will ever claim
            self.stats.inc("rejected_total", ("reason", "error"))
            await _respond_json(
                writer,
                500,
                {"error": f"model thread died: {self._worker_error!r}"},
                extra_headers=rid_header,
            )
            return
        try:
            fields = parse_generate_body(
                body,
                default_max_new_tokens=self.default_max_new_tokens,
                default_temperature=self.default_temperature,
                default_top_p=self.default_top_p,
            )
            req = Request(
                uid=self.admission.next_uid(),
                prompt=fields["prompt"],
                max_new_tokens=fields["max_new_tokens"],
                temperature=fields["temperature"],
                top_p=fields["top_p"],
                spec=fields["spec"],
                adapter=fields["adapter"],
            )
            # capacity/validity errors surface as 400 here, before admission,
            # instead of crashing the decode loop later
            self.scheduler.validate_request(req)
        except (BadRequest, ValueError) as e:
            self.stats.inc("rejected_total", ("reason", "bad_request"))
            await _respond_json(writer, 400, {"error": str(e)}, extra_headers=rid_header)
            return

        loop = asyncio.get_running_loop()
        events: "asyncio.Queue[Tuple[str, Any, Any]]" = asyncio.Queue()

        def post(kind: str, a: Any = None, b: Any = None) -> None:
            self._post(loop, events, (kind, a, b))

        deadline = (
            time.monotonic() + fields["deadline_s"]
            if fields["deadline_s"] is not None
            else None
        )
        # root span for the whole request; queue_wait opens now and is ended
        # by the model thread when it claims the ticket (cross-thread span)
        root = self.tracer.start_span(
            "request", trace_id=rid, uid=req.uid, route="generate",
            prompt_tokens=len(req.prompt),
        )
        ticket = Ticket(
            uid=req.uid,
            request=req,
            deadline=deadline,
            on_token=lambda uid, tok, idx: post("token", tok, idx),
            on_finish=lambda completion: post("finish", completion),
            trace_id=rid,
            span=root,
            queue_span=self.tracer.start_span(
                "queue_wait", trace_id=rid, parent=root, uid=req.uid
            ),
        )
        try:
            self.admission.try_admit(ticket)
        except QueueFull as e:
            self.stats.inc("rejected_total", ("reason", "queue_full"))
            ticket.queue_span.set(outcome="queue_full").end()
            root.set(finish_reason="rejected_queue_full").end()
            await _respond_json(
                writer,
                429,
                {"error": str(e)},
                extra_headers={
                    "Retry-After": f"{self.admission.retry_after_s:.0f}",
                    **rid_header,
                },
            )
            return
        except Draining as e:
            self.stats.inc("rejected_total", ("reason", "draining"))
            ticket.queue_span.set(outcome="draining").end()
            root.set(finish_reason="rejected_draining").end()
            await _respond_json(
                writer,
                503,
                {"error": str(e)},
                extra_headers={
                    "Retry-After": f"{self.admission.retry_after_s:.0f}",
                    **rid_header,
                },
            )
            return

        if fields["stream"]:
            await self._stream_response(reader, writer, ticket, events)
        else:
            await self._unary_response(reader, writer, ticket, events)

    async def _stream_response(self, reader, writer, ticket, events) -> None:
        writer.write(
            _head(
                200,
                "OK",
                "text/event-stream",
                {
                    "Cache-Control": "no-cache",
                    "X-Request-Id": ticket.trace_id or "",
                    # which weights serve this stream: a canary client can
                    # assert it hit the post-swap version without a healthz
                    # round trip (the version cannot change mid-request —
                    # swaps only happen with zero slots active)
                    "X-Relora-Weights": str(self.weights_version),
                },
            )
        )
        await writer.drain()
        eof_watch = asyncio.ensure_future(reader.read(1))
        try:
            while True:
                getter = asyncio.ensure_future(events.get())
                done, _ = await asyncio.wait(
                    {getter, eof_watch}, return_when=asyncio.FIRST_COMPLETED
                )
                if eof_watch in done and getter not in done:
                    getter.cancel()
                    self._client_gone(ticket)
                    return
                kind, a, b = getter.result()
                if kind == "token":
                    event = {"uid": ticket.uid, "index": b, "token": a}
                    # a span for the first token's write only, the last hop of
                    # the request's TTFT: one a streamed token turned the
                    # flight recorder's ring over in two seconds.  Manual,
                    # explicit parent: handlers interleave on one thread, so
                    # the tracer's ambient (thread-local) nesting would
                    # cross-wire concurrent streams
                    flush = (
                        self.tracer.start_span(
                            "sse_flush", trace_id=ticket.trace_id, parent=ticket.span, index=b
                        )
                        if b == 0
                        else None
                    )
                    t_write = self.tracer.clock()
                    writer.write(_sse(event))
                    try:
                        await writer.drain()
                    except (ConnectionError, OSError):
                        if flush is not None:
                            flush.set(outcome="disconnect").end()
                        self._client_gone(ticket)
                        return
                    if flush is not None:
                        flush.end()
                    self.stats.observe("sse_flush_seconds", self.tracer.clock() - t_write)
                else:  # finish
                    writer.write(_sse(_completion_record(a)))
                    writer.write(b"data: [DONE]\n\n")
                    await writer.drain()
                    return
        finally:
            if not eof_watch.done():
                eof_watch.cancel()

    async def _unary_response(self, reader, writer, ticket, events) -> None:
        eof_watch = asyncio.ensure_future(reader.read(1))
        try:
            while True:
                getter = asyncio.ensure_future(events.get())
                done, _ = await asyncio.wait(
                    {getter, eof_watch}, return_when=asyncio.FIRST_COMPLETED
                )
                if eof_watch in done and getter not in done:
                    getter.cancel()
                    self._client_gone(ticket)
                    return
                kind, a, _b = getter.result()
                if kind == "finish":
                    await _respond_json(
                        writer,
                        500 if a.finish_reason == "error" else 200,
                        _completion_record(a),
                        extra_headers={
                            "X-Request-Id": ticket.trace_id or "",
                            "X-Relora-Weights": str(self.weights_version),
                        },
                    )
                    return
        finally:
            if not eof_watch.done():
                eof_watch.cancel()

    def _client_gone(self, ticket: Ticket) -> None:
        """The client disconnected mid-request: flag the ticket so the model
        thread frees its slot at the next step boundary."""
        ticket.cancelled.set()
        self.stats.inc("disconnects_total")


def run_server(
    scheduler: ContinuousBatchingScheduler,
    *,
    host: str = "127.0.0.1",
    port: int = 8000,
    ready_cb: Optional[Callable[["GenerateServer"], None]] = None,
    **kwargs: Any,
) -> int:
    """Blocking entry point for the CLI: build a GenerateServer, run it until
    a SIGTERM drain completes.  ``ready_cb(server)`` fires once the listener
    is bound (the CLI writes the chosen port for --port 0)."""
    server = GenerateServer(scheduler, host=host, port=port, **kwargs)

    async def _main() -> None:
        serve = asyncio.ensure_future(server.serve_forever())
        while not server.started.is_set():
            await asyncio.sleep(0.01)
            if serve.done():
                break
        if ready_cb is not None and not serve.done():
            ready_cb(server)
        await serve

    asyncio.run(_main())
    return 0
