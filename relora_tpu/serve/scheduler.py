"""Slot-based continuous batching over a preallocated decode cache.

The decode step is compiled once for a fixed ``(max_batch, cache_size)`` cache
and keeps running as requests come and go — no retracing on admission or
eviction, which is the property that makes continuous batching cheap on
XLA-compiled accelerators:

- **Admit**: a new request prefills alone (bucketed lengths, so a handful of
  prefill compilations total), then ``engine.insert`` copies its single-row
  cache into a free slot of the persistent batch cache; its first sampled
  token and position join the step's token/pos arrays.
- **Step**: one jitted decode for all ``max_batch`` slots, occupied or not —
  a free slot decodes garbage at position 0, which is invisible (the
  ``j <= position`` mask) and overwritten by the next admission's insert.
- **Evict**: a row that hits EOS or its token budget is simply marked free;
  the arrays keep their shape, so nothing recompiles.

Sampling stays deterministic per request regardless of batch composition:
each row draws from a key folded from ``(request id, token index)``, never
from the slot index or the global step — the batched greedy drain is
token-identical to unbatched decode, and sampled requests reproduce across
different interleavings.

The scheduler is an *incremental* core so an online front-end
(serve/server.py) can drive it one round at a time:

- ``submit(req)`` queues a validated request (optionally with per-token /
  completion callbacks and an absolute deadline);
- ``step()`` performs one admit-plus-decode round and returns the requests
  that finished during it;
- ``cancel(uid)`` frees a request's slot mid-decode (client disconnects),
  returning a partial completion;
- ``run(requests)`` is a thin drain wrapper — submit everything, step until
  idle — preserving the original batch CLI behavior exactly.

The scheduler is single-threaded by design: all of ``submit``/``step``/
``cancel`` must be called from one thread (the server's model thread);
cross-thread admission is the AdmissionController's job (serve/admission.py).

Per-request latency and throughput go to the existing metrics.jsonl sink
(utils/logging.MetricsLogger): ``serve_request`` records with time-to-first-
token, total latency, and decode tokens/sec, plus one ``serve/queue_depth``
/ ``serve/active_slots`` gauge record per decode step so load tooling and
the ``/metrics`` endpoint have a per-step signal.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, Iterable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from relora_tpu.models.step import PAGED, RING, check_refused
from relora_tpu.obs.tracer import NoopTracer
from relora_tpu.serve import wire
from relora_tpu.serve.engine import InferenceEngine, bucket_length
from relora_tpu.serve.paging import PageAllocator, PrefixCache, pages_needed
from relora_tpu.serve.sampling import PATHS, SamplingParams, batch_path, spec_verify_draws
from relora_tpu.utils import faults
from relora_tpu.utils.logging import MetricsLogger, get_logger

logger = get_logger(__name__)

#: uid, token id, token index within the generation (0 = first sampled token)
TokenCallback = Callable[[int, int, int], None]
#: called exactly once per request with its Completion
FinishCallback = Callable[["Completion"], None]


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request: token-id prompt plus per-request sampling.
    ``top_k`` is batch-global (static shape) and lives on the scheduler.
    ``spec`` opts this request out of speculative drafting (``False``) when
    the scheduler runs with it on — output distribution is identical either
    way; turning it off just skips the draft/verify work for this row.
    ``adapter`` names a tenant LoRA adapter (serve/adapters.py registry);
    ``None`` decodes the base model (slot 0, the identity adapter)."""

    uid: int
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_p: float = 1.0
    spec: bool = True
    adapter: Optional[str] = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    finish_reason: str  # "eos" | "length" | "timeout" | "cancelled" | "error"
    prompt_tokens: int
    ttft_s: float
    latency_s: float
    error: Optional[str] = None  # reader-facing detail when finish_reason="error"


@dataclasses.dataclass
class _Slot:
    request: Request
    pos: int  # absolute position of the next cache write
    tokens: List[int]
    t_admit: float
    t_first: float
    deadline: Optional[float] = None  # absolute time.monotonic(), None = no limit
    span: Optional[Any] = None  # per-request "decode" span; ended at retire
    adapter_slot: int = 0  # HBM slot this request's adapter is pinned to


class ContinuousBatchingScheduler:
    """Drains a stream of requests through ``max_batch`` decode slots."""

    def __init__(
        self,
        engine: InferenceEngine,
        *,
        max_batch: int,
        eos_id: Optional[int] = None,
        top_k: int = 0,
        metrics: Optional[MetricsLogger] = None,
        key: Optional[jax.Array] = None,
        tracer: Optional[Any] = None,
        obs_registry: Optional[Any] = None,
        adapter_registry: Optional[Any] = None,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if adapter_registry is not None and not getattr(engine, "adapter_slots", 0):
            raise ValueError(
                "adapter_registry needs an engine built with adapter_slots "
                "(the stacked multi-tenant LoRA layout)"
            )
        self.engine = engine
        self.max_batch = max_batch
        self.eos_id = eos_id
        self.top_k = top_k
        self.metrics = metrics
        self.adapter_registry = adapter_registry
        # tracing defaults to no-op so the batch CLI pays nothing; the HTTP
        # server injects its Tracer + ServeMetrics (per-phase histograms)
        self.tracer = tracer if tracer is not None else NoopTracer()
        self.obs_registry = obs_registry
        ContinuousBatchingScheduler.publish_constants(self)  # a subclass adds its own once it is built
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self._step_count = 0
        self._pending: Deque[Request] = deque()
        self._slots: List[Optional[_Slot]] = [None] * max_batch
        self._cache = None  # allocated on first admission, then persistent
        self._tokens = np.zeros(max_batch, np.int32)
        self._positions = np.zeros(max_batch, np.int32)
        # per-row adapter slot indices for the grouped LoRA kernel; free rows
        # point at slot 0 (the identity adapter) so their garbage decode is
        # pure base-model work
        self._adapter_row = np.zeros(max_batch, np.int32)
        self._deadlines: Dict[int, float] = {}
        self._on_token: Dict[int, TokenCallback] = {}
        self._on_finish: Dict[int, FinishCallback] = {}
        self._trace_ids: Dict[int, str] = {}  # uid -> request trace id

    # -- incremental API ------------------------------------------------------

    def validate_request(self, req: Request) -> None:
        """Reject requests the decode loop could not serve: empty prompts and
        prompts whose generation cannot fit the cache.  The server maps this
        ``ValueError`` to HTTP 400; ``run()`` raises it from its preamble."""
        need = len(req.prompt) + req.max_new_tokens
        if len(req.prompt) < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        if need > self.engine.cache_size:
            raise ValueError(
                f"request {req.uid} needs {need} cache entries, "
                f"capacity is {self.engine.cache_size}"
            )
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.uid}: max_new_tokens must be >= 1, got {req.max_new_tokens}"
            )
        if req.adapter is not None:
            if self.adapter_registry is None:
                raise ValueError(
                    f"request {req.uid}: server is not running with an adapter "
                    "registry (--adapter-dir); 'adapter' is not accepted"
                )
            if not self.adapter_registry.known(req.adapter):
                raise ValueError(
                    f"request {req.uid}: unknown adapter {req.adapter!r}"
                )

    def submit(
        self,
        req: Request,
        *,
        on_token: Optional[TokenCallback] = None,
        on_finish: Optional[FinishCallback] = None,
        deadline: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> None:
        """Queue a request for admission at the next ``step()``.

        ``on_token(uid, token, index)`` fires for every token as it is
        sampled (index 0 is the prefill's first token); ``on_finish`` fires
        exactly once with the Completion.  ``deadline`` is an absolute
        ``time.monotonic()`` bound — a request still decoding past it
        finishes with its partial output and reason ``"timeout"``.
        ``trace_id`` threads the caller's request id onto every phase span
        this request produces (prefill/insert/decode)."""
        self.validate_request(req)
        if req.uid in self._deadlines or req.uid in self._on_finish or any(
            r.uid == req.uid for r in self._pending
        ) or any(s is not None and s.request.uid == req.uid for s in self._slots):
            raise ValueError(f"request {req.uid}: uid already in flight")
        if deadline is not None:
            self._deadlines[req.uid] = deadline
        if on_token is not None:
            self._on_token[req.uid] = on_token
        if on_finish is not None:
            self._on_finish[req.uid] = on_finish
        if trace_id is not None:
            self._trace_ids[req.uid] = trace_id
        self._pending.append(req)

    def _observe(self, name: str, value: float) -> None:
        if self.obs_registry is not None:
            self.obs_registry.observe(name, value)

    def publish_constants(self) -> None:
        """Publish what changes only with the tree or the pool — here the
        ``serve/param_bytes`` gauge, the bytes of the weights as the engine
        holds them.  Called when a registry is attached and after a reload,
        never a round."""
        if self.obs_registry is not None:
            self.obs_registry.set_gauge("param_bytes", self.engine.param_bytes())

    def drop_host_gap(self) -> None:
        """The driving loop waited with nothing to run: that wait is no
        host gap (the paged scheduler counts one; here there is none)."""

    def cancel(
        self, uid: int, reason: str = "cancelled", detail: Optional[str] = None
    ) -> Optional[Completion]:
        """Free a request's slot (or drop it from the pending queue) and
        report its partial output.  Returns the Completion, or None when the
        uid is unknown (already finished — cancellation raced completion)."""
        for req in list(self._pending):
            if req.uid == uid:
                self._pending.remove(req)
                return self._finalize_unadmitted(req, reason, detail)
        for slot_idx, slot in enumerate(self._slots):
            if slot is not None and slot.request.uid == uid:
                return self._retire(slot_idx, reason, detail)
        return None

    def fail_all(
        self, reason: str = "error", detail: Optional[str] = None
    ) -> List[Completion]:
        """Terminally complete every queued and active request — the
        model-thread-death path.  Each request gets whatever tokens it
        already produced plus ``finish_reason=reason`` (callbacks fire as
        usual), so no stream is ever left hanging on a dead worker.  Pure
        host-side bookkeeping: never touches the device, so it is safe to
        call after the jitted step itself blew up."""
        completions: List[Completion] = []
        for req in list(self._pending):
            self._pending.remove(req)
            completions.append(self._finalize_unadmitted(req, reason, detail))
        for slot_idx, slot in enumerate(self._slots):
            if slot is not None:
                completions.append(self._retire(slot_idx, reason, detail))
        return completions

    def has_work(self) -> bool:
        return bool(self._pending) or any(s is not None for s in self._slots)

    @property
    def queue_depth(self) -> int:
        return len(self._pending)

    @property
    def active_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    def adapter_stats(self) -> Optional[Dict[str, Any]]:
        """Registry occupancy/churn counters for /healthz, or None when the
        server runs without multi-tenant adapters."""
        if self.adapter_registry is None:
            return None
        return self.adapter_registry.stats()

    def step(self) -> List[Completion]:
        """One admit-plus-decode round: expire deadlines, fill free slots
        from the pending queue, then run one jitted decode over all slots.
        Returns the requests that finished during the round (possibly at
        admission, when the first token already satisfies the request)."""
        finished: List[Completion] = []
        # admission (prefill + insert) runs on the decode loop's critical
        # path: its share of the step is the "prefill stall" every in-flight
        # stream pays, reported per step next to the batch-fill ratio
        t_step = time.monotonic()
        self._expire_deadlines(finished)
        while True:
            self._admit_pass(finished)
            if any(s is not None for s in self._slots) or not self._pending:
                break
            # everything admitted this round finished at once; keep admitting
            # (mirrors the original drain loop's `continue` back to admission)
        admit_s = time.monotonic() - t_step
        if not any(s is not None for s in self._slots):
            return finished

        # -- one decode step over all slots ----------------------------------
        # batch-level span (several requests share it): dispatch + the bulk
        # token pull, which is the step's device sync point
        t_decode = time.monotonic()
        n_active = self.active_slots  # the batch this decode step runs over
        with self.tracer.span(
            "decode_step", step=self._step_count, active_slots=n_active
        ):
            logits, self._cache = self.engine.decode(
                self._cache,
                self._tokens[:, None],
                self._positions[:, None],
                adapter_idx=self._adapter_row,
            )
            self._step_count += 1
            # one bulk pull for the whole batch, then plain Python ints —
            # per-slot int(next_tokens[i]) would be a device sync per row
            next_tokens = self._sample_rows(logits, self._slots).tolist()
        decode_s = time.monotonic() - t_decode
        self._observe("decode_step_seconds", decode_s)
        # utilization attribution: how full the decode batch actually was,
        # and what share of the step admissions stole from decoding
        batch_fill = n_active / self.max_batch
        stall_share = admit_s / max(admit_s + decode_s, 1e-9)
        if self.obs_registry is not None:
            self.obs_registry.set_gauge("batch_fill", batch_fill)
            self.obs_registry.set_gauge("prefill_stall_share", stall_share)
        for slot_idx, slot in enumerate(self._slots):
            if slot is None:
                continue
            tok = next_tokens[slot_idx]
            slot.tokens.append(tok)
            slot.pos += 1
            self._tokens[slot_idx] = tok
            self._positions[slot_idx] = slot.pos
            self._emit_token(slot.request.uid, tok, len(slot.tokens) - 1)
            self._finish_if_done(slot_idx, finished)
        record = None
        if self.metrics is not None:
            watcher = getattr(self.engine, "compile_watcher", None)
            record = {
                "serve/decode_step": self._step_count,
                "serve/queue_depth": len(self._pending),
                "serve/active_slots": self.active_slots,
                "serve/batch_fill": round(batch_fill, 4),
                "serve/prefill_stall_s": round(admit_s, 6),
                "serve/prefill_stall_share": round(stall_share, 4),
                # a nonzero here after warmup means a shape escaped the
                # warmed buckets — see docs/operations.md troubleshooting
                "compile/steady_state_retraces": (
                    watcher.steady_state_retraces if watcher is not None else 0
                ),
            }
        self._adapter_gauges(record)
        if record is not None:
            self.metrics.log(record)
        return finished

    def run(self, requests: Iterable[Request]) -> Dict[int, Completion]:
        """Admit-and-decode until every request completes.  Returns
        completions keyed by ``Request.uid``."""
        incoming = list(requests)
        for req in incoming:
            # validate everything before admitting anything, so a bad request
            # raises without leaving earlier ones queued on the scheduler
            self.validate_request(req)
        for req in incoming:
            self.submit(req)
        completions: Dict[int, Completion] = {}
        t_start = time.monotonic()
        while self.has_work():
            for completion in self.step():
                completions[completion.uid] = completion
        logger.info(
            f"drained {len(completions)} requests in {time.monotonic() - t_start:.2f}s "
            f"({self._step_count} decode steps)"
        )
        return completions

    # -- internals -----------------------------------------------------------

    def _expire_deadlines(self, finished: List[Completion]) -> None:
        if not self._deadlines:
            return
        now = time.monotonic()
        for slot_idx, slot in enumerate(self._slots):
            if slot is not None and slot.deadline is not None and now >= slot.deadline:
                finished.append(self._retire(slot_idx, "timeout"))

    def _admit_pass(self, finished: List[Completion]) -> None:
        for slot_idx in range(self.max_batch):
            if self._slots[slot_idx] is not None or not self._pending:
                continue
            req = self._pending.popleft()
            deadline = self._deadlines.get(req.uid)
            if deadline is not None and time.monotonic() >= deadline:
                # expired while queued: report the timeout without spending a
                # prefill on it; the slot stays free for the next admission
                finished.append(self._finalize_unadmitted(req, "timeout"))
                continue
            try:
                adapter_slot = self._acquire_adapter(req)
            except Exception as e:
                logger.warning(f"request {req.uid}: adapter load failed: {e!r}")
                finished.append(
                    self._finalize_unadmitted(req, "error", f"adapter load failed: {e}")
                )
                continue
            if adapter_slot is None:
                # every adapter slot pinned by live traffic: stay queued
                # (FIFO — later requests do not jump the head) and retry
                # after a retirement drops a pin
                self._pending.appendleft(req)
                return
            t_admit = time.monotonic()
            self._cache, first = self._admit(
                req, slot_idx, self._ensure_cache(), adapter_slot
            )
            self._slots[slot_idx] = _Slot(
                request=req,
                pos=len(req.prompt),
                tokens=[first],
                t_admit=t_admit,
                t_first=time.monotonic(),
                deadline=deadline,
                # the request's decode phase: open until EOS/budget/cancel
                span=self.tracer.start_span(
                    "decode", trace_id=self._trace_ids.get(req.uid), uid=req.uid
                ),
                adapter_slot=adapter_slot,
            )
            self._tokens[slot_idx] = first
            self._positions[slot_idx] = len(req.prompt)
            self._adapter_row[slot_idx] = adapter_slot
            self._emit_token(req.uid, first, 0)
            self._finish_if_done(slot_idx, finished)

    def _ensure_cache(self):
        if self._cache is None:
            self._cache = self.engine.init_cache(self.max_batch)
        return self._cache

    def _acquire_adapter(self, req: Request) -> Optional[int]:
        """Pin the request's adapter for admission.  Returns its HBM slot
        index, or ``None`` when every slot is pinned by live traffic — the
        caller keeps the request queued and retries next round (the prefix
        cache's evict-then-retry contract).  Raises when the adapter fails
        to load (bad checkpoint dir)."""
        if self.adapter_registry is None:
            return 0
        return self.adapter_registry.acquire(req.adapter)

    def _release_adapter(self, req: Request) -> None:
        if self.adapter_registry is not None and req.adapter is not None:
            self.adapter_registry.release(req.adapter)

    def _count_adapter_request(self, req: Request) -> None:
        if self.adapter_registry is not None and self.obs_registry is not None:
            self.obs_registry.inc(
                "adapter_requests_total", label=("adapter", req.adapter or "base")
            )

    def _adapter_gauges(self, record: Optional[Dict[str, Any]] = None) -> None:
        """Publish registry occupancy next to the step's other gauges (and
        into the step's metrics.jsonl record when one is being built)."""
        if self.adapter_registry is None:
            return
        stats = self.adapter_registry.stats()
        if self.obs_registry is not None:
            self.obs_registry.set_gauge("adapter_slots_used", stats["slots_used"])
            self.obs_registry.set_gauge("adapter_hit_rate", stats["hit_rate"])
        if record is not None:
            record["serve/adapter_slots_used"] = stats["slots_used"]
            record["serve/adapter_evictions_total"] = stats["evictions_total"]
            record["serve/adapter_hit_rate"] = stats["hit_rate"]

    def _admit(self, req: Request, slot_idx: int, cache, adapter_slot: int = 0):
        """Prefill one request (batch of 1, bucketed length) and copy its
        cache row into ``slot_idx``.  Returns (cache, first sampled token)."""
        L = len(req.prompt)
        T = min(bucket_length(L), self.engine.cache_size)
        ids = np.zeros((1, T), np.int32)
        ids[0, :L] = np.asarray(req.prompt, np.int32)
        tid = self._trace_ids.get(req.uid)
        # the prefill span includes the first-token sample pull: that host
        # pull is the sync point, so the span covers real compute, not just
        # async dispatch
        t0 = time.monotonic()
        with self.tracer.span(
            "prefill", trace_id=tid, uid=req.uid, prompt_tokens=L, bucket=T
        ):
            logits, pcache = self.engine.prefill(
                jnp.asarray(ids),
                adapter_idx=np.array([adapter_slot], np.int32),
            )
            first = self._sample_first(logits[:, L - 1, :], req)
            first_id = int(np.asarray(first)[0])
        t1 = time.monotonic()
        self._observe("prefill_seconds", t1 - t0)
        with self.tracer.span("insert", trace_id=tid, uid=req.uid, slot=slot_idx):
            cache = self.engine.insert(cache, pcache, slot_idx)
        self._observe("insert_seconds", time.monotonic() - t1)
        return cache, first_id

    def _sample_rows(self, logits, slots) -> jax.Array:
        """Every row's draw, enqueued and not waited for: the caller's read
        of it is the round's one bulk pull (the paged rounds time the two
        apart, as ``dispatch`` and ``pull``).  A row with no slot is drawn
        greedily and its token discarded.  The ``sample`` span is this call,
        and carries the batch's ``path``."""
        with self.tracer.span("sample") as span:
            return self._draw(
                logits, [None if s is None else (s.request, len(s.tokens)) for s in slots], span
            )

    def _sample_first(self, logits, req: Request) -> jax.Array:
        """A request's first token from the ``(1, V)`` logits of its prompt's
        last position: the same sampler, one row, token index 0."""
        return self._draw(logits, [(req, 0)])

    def _draw(self, logits, rows, span=None) -> jax.Array:
        """One dispatch for ``rows`` of ``(request, token index)``.  A draw is
        keyed by (uid, token index), so a request's sample stream does not
        depend on which slot it landed in or what shares its batch; the host
        only fills numpy vectors, and the keys are built inside the sampler's
        program (``sampling.sample_rows``).  uids are uint32, the type
        ``fold_in`` gives its data.  The program branches on what the batch
        needs (``sampling.batch_path``); the same predicate over the same
        vectors labels ``sample_draws_total`` and ``span``."""
        B = len(rows)
        uids = np.zeros(B, np.uint32)
        token_index = np.zeros(B, np.int32)
        temps = np.zeros(B, np.float32)
        top_ps = np.ones(B, np.float32)
        for i, row in enumerate(rows):
            if row is None:
                continue
            req, index = row
            uids[i] = req.uid
            token_index[i] = index
            temps[i] = req.temperature
            top_ps[i] = req.top_p
        path = PATHS[batch_path(temps, top_ps)]  # numpy in, a numpy integer out
        if self.obs_registry is not None:
            self.obs_registry.inc("sample_draws_total", label=("path", path))
        if span is not None:
            span.set(path=path)
        return self.engine._sample_rows(
            logits,
            self.key,
            uids,
            token_index,
            temperature=temps,
            top_k=self.top_k,
            top_p=top_ps,
        )

    def _emit_token(self, uid: int, token: int, index: int) -> None:
        callback = self._on_token.get(uid)
        if callback is None:
            return
        try:
            callback(uid, token, index)
        except Exception as e:  # a dead stream must not kill the decode loop
            logger.warning(f"request {uid}: token callback failed: {e!r}")
            self._on_token.pop(uid, None)

    def _finish_if_done(self, slot_idx: int, finished: List[Completion]) -> None:
        slot = self._slots[slot_idx]
        req = slot.request
        last = slot.tokens[-1]
        reason = None
        if self.eos_id is not None and last == self.eos_id:
            reason = "eos"
        elif len(slot.tokens) >= req.max_new_tokens:
            reason = "length"
        if reason is None:
            return
        finished.append(self._retire(slot_idx, reason))

    def _retire(
        self, slot_idx: int, reason: str, detail: Optional[str] = None
    ) -> Completion:
        """Evict a slot (EOS / budget / timeout / cancel / error): build the
        Completion, free the row — nothing recompiles — and notify."""
        slot = self._slots[slot_idx]
        req = slot.request
        now = time.monotonic()
        completion = Completion(
            uid=req.uid,
            tokens=list(slot.tokens),
            finish_reason=reason,
            prompt_tokens=len(req.prompt),
            ttft_s=slot.t_first - slot.t_admit,
            latency_s=now - slot.t_admit,
            error=detail,
        )
        self._slots[slot_idx] = None  # evict: slot is free, nothing recompiles
        self._adapter_row[slot_idx] = 0  # free rows decode the identity adapter
        self._release_adapter(req)
        self._count_adapter_request(req)
        if slot.span is not None:
            slot.span.set(
                finish_reason=reason, output_tokens=len(completion.tokens)
            ).end()
            self._observe("decode_seconds", now - slot.t_first)
        if self.metrics is not None:
            decode_s = max(now - slot.t_first, 1e-9)
            self.metrics.log(
                {
                    "serve_request": req.uid,
                    "serve/prompt_tokens": completion.prompt_tokens,
                    "serve/output_tokens": len(completion.tokens),
                    "serve/finish_reason": reason,
                    "serve/ttft_s": completion.ttft_s,
                    "serve/latency_s": completion.latency_s,
                    "serve/decode_tokens_per_s": (len(completion.tokens) - 1) / decode_s
                    if len(completion.tokens) > 1
                    else 0.0,
                }
            )
        self._finalize(completion)
        return completion

    def _finalize_unadmitted(
        self, req: Request, reason: str, detail: Optional[str] = None
    ) -> Completion:
        """A request that never reached a slot (cancelled or expired while
        queued): empty output, zero latency fields."""
        self._count_adapter_request(req)
        completion = Completion(
            uid=req.uid,
            tokens=[],
            finish_reason=reason,
            prompt_tokens=len(req.prompt),
            ttft_s=0.0,
            latency_s=0.0,
            error=detail,
        )
        if self.metrics is not None:
            self.metrics.log(
                {
                    "serve_request": req.uid,
                    "serve/prompt_tokens": completion.prompt_tokens,
                    "serve/output_tokens": 0,
                    "serve/finish_reason": reason,
                    "serve/ttft_s": 0.0,
                    "serve/latency_s": 0.0,
                    "serve/decode_tokens_per_s": 0.0,
                }
            )
        self._finalize(completion)
        return completion

    def _finalize(self, completion: Completion) -> None:
        self._deadlines.pop(completion.uid, None)
        self._on_token.pop(completion.uid, None)
        self._trace_ids.pop(completion.uid, None)
        callback = self._on_finish.pop(completion.uid, None)
        if callback is None:
            return
        try:
            callback(completion)
        except Exception as e:
            logger.warning(
                f"request {completion.uid}: finish callback failed: {e!r}"
            )


def _one_expert_bytes(params) -> int:
    """Bytes of one routed expert's weights as the engine holds them (its
    slice of the first ``gate_up`` and ``down`` stacks); 0 without experts."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        if jax.tree_util.keystr(path).endswith("['gate_up']"):
            return 3 * leaf.shape[1] * (leaf.shape[2] // 2) * leaf.dtype.itemsize
    return 0


@dataclasses.dataclass
class _PagedSlot(_Slot):
    pages: List[int] = dataclasses.field(default_factory=list)  # logical order
    shared_pages: int = 0  # leading pages borrowed from the prefix cache
    prefill_progress: int = 0  # prompt tokens already written to the pool
    decoding: bool = False  # first token sampled; joins the decode batch
    seq: int = 0  # admission order; chunk scheduling is oldest-first
    migrating: bool = False  # handoff to a decode-pool peer is in flight
    # spec="model": the draft model's own page run (same worst-case size as
    # the base's), allocated at admission from the one shared pool
    draft_pages: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _SentChunk:
    """A prefill chunk that has been enqueued: whose it is and, where it ends
    the prompt, the first token's draw — on the device until ``first_id`` is
    pulled (in the chunk's own span, or at the next round's start for a chunk
    sent ahead)."""

    slot_idx: int
    slot: _PagedSlot
    first: Optional[jax.Array] = None
    first_id: Optional[int] = None


class PagedContinuousBatchingScheduler(ContinuousBatchingScheduler):
    """Continuous batching over the paged engine: budgeted rounds instead of
    prefill-on-admission.

    Each ``step()`` spends its budget as: expire deadlines, admit pending
    requests (page allocation + prefix-cache lookup only — cheap host work),
    run **at most one prefill chunk** for the oldest still-prefilling slot,
    then one paged decode over every decoding slot.  A long prompt therefore
    never stalls in-flight streams for more than one ``chunk_size`` forward —
    the contiguous scheduler's ``serve/prefill_stall_share`` is exactly the
    cost this removes.

    **The chunk goes ahead.**  Where a slot is still prefilling when a round's
    decode has been enqueued, the chunk the next round would have run is
    enqueued right behind it, *before* the decode is pulled: the device works
    on it while the host reads the tokens, commits them and launches the next
    decode.  The programs reach the device in the same order either way
    (decode n, chunk n+1, decode n+1) and one chunk a decode stays the
    policy, so every token is the one the serial order served; only the
    host's enqueue moves.  The next round finds the chunk sent, runs no other
    and, if it ended a prompt, pulls its first token just before its decode.

    Admission is all-or-nothing on pages (worst case
    ``ceil((prompt + max_new_tokens) / page_size)``): when the pool is
    exhausted the queue head *stays queued* (FIFO — later requests do not
    jump it) and is retried next round after retired requests or evicted
    prefix entries free pages.  Contrast with the HTTP front-end's 429 path,
    which only bounds the *queue*; allocator pressure never rejects.

    Sampling keys stay ``(uid, token_index)`` — the same stream as the
    contiguous scheduler — and the paged attention math is bitwise-identical
    to the contiguous path (ops/attention.paged_cached_attention), so a
    drain through this scheduler is token-identical to the contiguous one
    for the same request stream (pinned by tests/test_paging.py).

    ``spec="ngram"`` (engine built with ``spec_k >= 1``) turns each decode
    round into a draft→verify→accept round: a prompt-lookup drafter proposes
    up to ``spec_k`` continuation tokens per row from the row's own
    prompt+generated context, one ``(batch, spec_k+1)`` verify forward
    scores the whole window, and a host-side walk commits the longest
    accepted prefix plus one corrective token — so an accepting row emits up
    to ``spec_k+1`` tokens for one forward's worth of HBM traffic (decode is
    memory-bound; the window reuses the same weight/KV stream).  Greedy rows
    accept by argmax match, so their output is token-identical to the
    non-speculative path (pinned by tests/test_spec.py); sampled rows use
    rejection sampling against the same filtered target distribution
    ``sample()`` draws from, keyed by the same ``(uid, token_index)``
    scheme, so their outputs stay exactly target-distributed.  Rejected
    drafts need no pool rollback: every window write lands inside the
    request's worst-case admission allocation (or the null page, via the
    verify table's trailing null column) and is overwritten before any
    later query can attend it — page accounting is untouched, which
    tests/test_paging.py pins under cancel/expiry mid-stream.  A round
    where no row drafted falls back to the plain ``decode_paged`` shape, so
    both steady-state shapes are warmed and nothing retraces.

    ``packed=True`` (engine built with ``token_budget``) replaces the whole
    round with ONE ``step_paged`` dispatch: every decoding row's window plus
    oldest-first prefill tokens from *multiple* slots, token-budget
    (Sarathi) style, padded to the smallest warmed bucket.  Prefill no
    longer serializes one chunk per round, decode rides a compute-dense
    forward instead of a memory-bound ``(B, 1)`` step, and per-round
    dispatch overhead halves — while sampling reuses the sequential calls
    and keys verbatim, so the drain stays token-identical (pinned by
    tests/test_packed.py).
    """

    #: longest context suffix the prompt-lookup drafter tries to match
    _NGRAM_MAX = 3

    def __init__(
        self,
        engine: InferenceEngine,
        *,
        prefix_cache: bool = True,
        prefix_cache_entries: int = 256,
        spec: str = "off",
        packed: bool = False,
        role: str = "mixed",
        **kwargs,
    ):
        super().__init__(engine, **kwargs)
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be 'prefill', 'decode', or 'mixed', got {role!r}"
            )
        if spec not in ("off", "ngram", "model"):
            raise ValueError(
                f"spec must be 'off', 'ngram', or 'model', got {spec!r}"
            )
        if spec != "off" and getattr(engine, "spec_k", 0) < 1:
            raise ValueError(
                f"spec={spec!r} needs an engine built with spec_k >= 1 "
                "(the verify window compiles at (batch, spec_k+1))"
            )
        if spec == "model":
            if getattr(engine, "draft_params", None) is None:
                raise ValueError(
                    "spec='model' needs a draft model: call "
                    "engine.load_draft_params(...) before building the scheduler"
                )
            if packed:
                raise ValueError(
                    "spec='model' is incompatible with packed=True (the draft "
                    "proposal loop runs on the per-row decode path)"
                )
            if role != "mixed":
                raise ValueError(
                    "spec='model' needs role='mixed': draft KV pages cannot "
                    "migrate between disaggregated peers"
                )
            # base and draft prefill must stay in lockstep, so prefix-cache
            # page sharing (which skips base prefill work the draft still
            # needs) is disabled in model-drafted mode
            prefix_cache = False
        self._spec = spec
        self._spec_drafted = 0  # cumulative drafted tokens (counter)
        self._spec_accepted = 0  # cumulative accepted drafted tokens (counter)
        self._spec_sample = jax.jit(spec_verify_draws, static_argnames=("top_k",))
        if not getattr(engine, "paged", False):
            raise ValueError(
                "PagedContinuousBatchingScheduler needs an engine built with "
                "page_size/num_pages (got a contiguous InferenceEngine)"
            )
        asked = {
            "prefix reuse": prefix_cache,
            "speculation": spec != "off",
            "packed steps": packed,
            "page migration": role != "mixed",
        }
        check_refused(engine.config.family, getattr(engine, "refuses", ()), asked)
        self._packed = packed
        if packed:
            if not getattr(engine, "token_budget", 0):
                raise ValueError(
                    "packed=True needs an engine built with token_budget "
                    "(the packed step compiles at the budget's buckets)"
                )
            # every decoding row must fit its whole round window in one
            # dispatch — the budget only throttles prefill, never decode
            floor = self.max_batch * (
                engine.spec_k + 1 if spec == "ngram" else 1
            )
            if engine.token_budget < floor:
                raise ValueError(
                    f"token_budget ({engine.token_budget}) cannot hold every "
                    f"decode row's window: need >= {floor} "
                    f"(max_batch x window size)"
                )
        # one spec per cache kind of the model's layers (models/step.py): the
        # paged kind's pages are what admission allocates; a window layer's
        # ring is its slot's for good and costs a request nothing to hold
        specs = {c.kind: c for c in engine.cache_specs(self.max_batch)}
        self._paged_spec, self._ring_spec = specs[PAGED], specs.get(RING)
        ring_bytes = engine.pool_bytes(self.max_batch, RING)
        self._kv_cache_bytes = engine.pool_bytes(self.max_batch)
        self._kv_cache_bytes_by_kind = {PAGED: self._kv_cache_bytes - ring_bytes, RING: ring_bytes}
        self.allocator = PageAllocator(
            engine.num_pages,
            engine.page_size,
            page_bytes=self._kv_cache_bytes_by_kind[PAGED] // engine.num_pages,
        )
        self.prefix_cache = (
            PrefixCache(self.allocator, max_entries=prefix_cache_entries)
            if prefix_cache
            else None
        )
        self._pool = None  # allocated on first admission, then persistent
        # per-row decode block tables: NULL rows for free / still-prefilling
        # slots, so their garbage decode write lands in the null page
        self._tables = np.zeros((self.max_batch, engine.block_table_width), np.int32)
        # spec="model": per-row draft-model block tables, same null-row
        # convention as ``_tables`` (free rows stay all-null so the draft
        # loop's garbage writes land in the null page)
        self._draft_tables = np.zeros(
            (self.max_batch, engine.block_table_width), np.int32
        )
        # the packed step's table matrix: every slot's table (W plus the
        # trailing null column) and a final all-null pad row that padding
        # tokens' row_map points at — maintained from admission so packed
        # rounds never rebuild tables on the hot path
        self._ptables = np.zeros(
            (self.max_batch + 1, engine.block_table_width + 1), np.int32
        )
        self._admit_seq = 0  # admission order, drives chunk scheduling (FIFO)
        self._pad_tokens = 0  # chunk padding written, cumulative
        self._prefill_tokens = 0  # real prompt tokens written, cumulative
        # dispatch economics (cumulative): rounds, model dispatches, and
        # packed-window tokens (total vs real) — the gauges the packed step
        # exists to move (serve/dispatches_per_round, tokens_per_dispatch,
        # packed_token_utilization)
        self._round_total = 0
        self._dispatch_total = 0
        self._dispatch_tokens = 0
        self._dispatch_tokens_real = 0
        self._admit_time_s = 0.0  # cumulative prefill/admission wall time
        self._decode_time_s = 0.0  # cumulative decode/packed-step wall time
        # static for the engine's lifetime (pool shapes never change): the
        # serve/kv_cache_bytes (set above) and serve/kv_bytes_per_token gauges
        self._kv_bytes_per_token = engine.kv_bytes_per_token()
        # routed experts (ops/moe.py): the device's [local assignments,
        # distinct experts hit] of forwards not pulled yet — they ride the
        # next pull the tokens take — and what one expert's weights weigh
        self._moe_pending: List[Any] = []
        cfg = engine.config
        self._moe_fanout = cfg.num_experts_per_tok * sum(cfg.layer_moe)
        self._expert_bytes = _one_expert_bytes(engine.params)
        # table entries the last decode had to walk (the decode_step span's
        # live_pages; the serve/decode_live_page_share gauge)
        self._decode_live_pages = 0
        # decoding rows at or past the window in the last decode (the
        # decode_step span's rows_past_window; the serve/ring_wrapped_rows gauge)
        self._ring_wrapped_rows = 0
        # disaggregated serving (docs/serving.md): a prefill-role scheduler
        # hands each finished prompt's page run to ``migration_sink`` (set by
        # the server; runs on the model thread, must not block) and parks the
        # slot as ``migrating`` until the peer commits or the handoff fails
        # open back to local decode.  ``prefix_fetch`` pulls prefix pages
        # from a peer on a local cache miss (the fleet prefix directory).
        self.role = role
        self.migration_sink: Optional[Callable[[Dict[str, Any], list], bool]] = None
        self.prefix_fetch: Optional[Callable[[List[str]], Any]] = None
        self._prefix_fetch_tried: set = set()
        self._pages_migrated = 0
        self._migration_bytes = 0
        self._migration_failures = 0
        self._migrated_inserts = 0
        self._prefix_fetches = 0
        self._prefix_fetch_failures = 0
        # the chunk a decode_step sent ahead of its pull, until the next round
        # takes it up (cumulative count beside the dispatches')
        self._ahead: Optional[_SentChunk] = None
        self._chunks_ahead = 0
        # the host gap (docs/observability.md): when the last blocking pull
        # returned, on the tracer's clock (None: nothing to count from),
        # whether a chunk sent ahead was queued behind it (the device had
        # work: what passes is covered, not gap), and the seconds of each
        # kind counted so far for the round in progress
        self._pull_stamp: Optional[float] = None
        self._pull_covered = False
        self._host_gap_s = 0.0
        self._covered_gap_s = 0.0
        self.publish_constants()

    # -- admission ------------------------------------------------------------

    def _ensure_pool(self):
        if self._pool is None:
            self._pool = self.engine.init_pool(self.max_batch)
        return self._pool

    def _admit_pass(self, finished: List[Completion]) -> None:
        """Fill free slots from the queue head: prefix lookup + page
        allocation only (no device work — the prefill happens one chunk per
        step).  Allocation failure leaves the head queued and stops."""
        while self._pending:
            slot_idx = next(
                (i for i in range(self.max_batch) if self._slots[i] is None), None
            )
            if slot_idx is None:
                return
            req = self._pending[0]
            deadline = self._deadlines.get(req.uid)
            if deadline is not None and time.monotonic() >= deadline:
                self._pending.popleft()
                finished.append(self._finalize_unadmitted(req, "timeout"))
                continue
            try:
                adapter_slot = self._acquire_adapter(req)
            except Exception as e:
                logger.warning(f"request {req.uid}: adapter load failed: {e!r}")
                self._pending.popleft()
                finished.append(
                    self._finalize_unadmitted(req, "error", f"adapter load failed: {e}")
                )
                continue
            if adapter_slot is None:
                # every adapter slot pinned by live traffic: the head stays
                # queued (FIFO) and retries after a retirement drops a pin —
                # the same contract as allocator exhaustion below
                return
            need = pages_needed(
                len(req.prompt) + req.max_new_tokens, self.engine.page_size
            )
            shared_pages: List[int] = []
            shared_tokens = 0
            if self.prefix_cache is not None:
                shared_pages, shared_tokens = self._prefix_lookup(req.prompt)
                if (
                    not shared_pages
                    and self.prefix_fetch is not None
                    and req.uid not in self._prefix_fetch_tried
                ):
                    # one fetch attempt per uid: a miss (or a failed peer)
                    # falls open to local prefill, never a retry loop
                    if len(self._prefix_fetch_tried) > 8192:
                        self._prefix_fetch_tried.clear()
                    self._prefix_fetch_tried.add(req.uid)
                    shared_pages, shared_tokens = self._fetch_prefix(req)
            # spec="model": the draft model keeps its own KV pages in the one
            # shared pool — admission allocates both runs or neither
            draft_need = need if self._spec == "model" else 0
            fresh = self.allocator.alloc(need - len(shared_pages) + draft_need)
            if fresh is None and self.prefix_cache is not None:
                # under pressure: drop idle prefix entries (LRU) and retry —
                # entries shared with live requests survive via refcounts
                self.prefix_cache.evict(need - len(shared_pages) + draft_need)
                fresh = self.allocator.alloc(need - len(shared_pages) + draft_need)
            if fresh is None:
                # allocator exhausted: stay queued rather than reject; pages
                # free as decoding requests retire (docs/operations.md)
                if shared_pages:
                    self.allocator.decref(shared_pages)
                self._release_adapter(req)  # drop the pin while we wait
                return
            self._pending.popleft()
            t_admit = time.monotonic()
            base_fresh = fresh[: need - len(shared_pages)]
            draft_pages = fresh[need - len(shared_pages):]
            self._slots[slot_idx] = _PagedSlot(
                request=req,
                pos=0,
                tokens=[],
                t_admit=t_admit,
                t_first=t_admit,  # overwritten when the first token lands
                deadline=deadline,
                span=None,  # decode span opens at first token
                pages=shared_pages + base_fresh,
                shared_pages=len(shared_pages),
                prefill_progress=shared_tokens,
                seq=self._admit_seq,
                adapter_slot=adapter_slot,
                draft_pages=draft_pages,
            )
            self._admit_seq += 1
            # decode row stays NULL until this slot starts decoding
            self._tokens[slot_idx] = 0
            self._positions[slot_idx] = 0
            self._tables[slot_idx, :] = 0
            self._draft_tables[slot_idx, :] = 0
            # the packed table row is live from admission: prefill tokens
            # route through it the same round they are admitted
            self._ptables[slot_idx, :] = 0
            pages = shared_pages + base_fresh
            self._ptables[slot_idx, : len(pages)] = pages
            self._adapter_row[slot_idx] = adapter_slot

    # -- prefill (one chunk per round) ----------------------------------------

    def _prefill_pass(self, finished: List[Completion]) -> None:
        """Run one prefill chunk for the oldest still-prefilling slot; when
        it completes the prompt, sample the first token (key (uid, 0) — the
        same stream as the contiguous path) and arm the slot for decode."""
        sent = self._send_chunk()
        if sent is not None:
            self._land_chunk(sent, finished)

    def _send_chunk(self, ahead: bool = False) -> Optional[_SentChunk]:
        """Enqueue one chunk of the oldest still-prefilling slot's prompt
        and, where it ends the prompt, the first token's draw behind it.
        ``ahead``: from inside a ``decode_step``, behind a decode that has
        not been pulled — nothing is read here, the next round lands it
        (:meth:`_land_chunk`); otherwise a chunk that ends a prompt is pulled
        inside its own span."""
        prefilling = [
            (s.seq, i)
            for i, s in enumerate(self._slots)
            if s is not None and not s.decoding and not s.migrating
        ]
        if not prefilling:
            return None
        slot_idx = min(prefilling)[1]  # oldest admission first (FIFO)
        slot = self._slots[slot_idx]
        sent = _SentChunk(slot_idx, slot)
        req = slot.request
        L = len(req.prompt)
        chunk = self.engine.chunk_size
        start = slot.prefill_progress
        n_real = min(chunk, L - start)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :n_real] = list(req.prompt[start : start + n_real])
        table = np.zeros((1, self.engine.block_table_width), np.int32)
        table[0, : len(slot.pages)] = slot.pages
        self._pad_tokens += chunk - n_real
        self._prefill_tokens += n_real
        t0 = time.monotonic()
        # the request's trace, the round's child (the decode_step's, sent
        # ahead): one chunk of one request's prompt, inside the batch-level
        # round that ran it
        self._enqueue()
        with self.tracer.span(
            "prefill_chunk", trace_id=self._trace_ids.get(req.uid), parent=self.tracer.current_span(),
            uid=req.uid, start=start, chunk=chunk, real=n_real, **({"ahead": 1} if ahead else {}),
        ) as sp_chunk:
            logits, self._pool = self.engine.prefill_chunk(
                jnp.asarray(ids), start, self._ensure_pool(), table,
                adapter_idx=np.full(1, slot.adapter_slot, np.int32), slot=slot_idx,
            )
            self._count_dispatch(chunk, n_real)
            if self._spec == "model":
                # the draft model prefills the same chunk into its own page
                # run, so base and draft KV stay in lockstep position-wise
                draft_table = np.zeros((1, self.engine.block_table_width), np.int32)
                draft_table[0, : len(slot.draft_pages)] = slot.draft_pages
                _, self._pool = self.engine.draft_prefill_chunk(
                    ids, start, self._pool, draft_table
                )
                self._count_dispatch(chunk, n_real)
            slot.prefill_progress = start + n_real
            if slot.prefill_progress >= L:
                sent.first = self._sample_first(logits[:, L - 1 - start, :], req)
                if not ahead:
                    # the chunk that ends a prompt is pulled anyway: its own
                    # counts go on its span (an earlier chunk's reach the
                    # counters with the next pull)
                    with self.tracer.span("pull"):
                        sent.first_id = self._pull_first(sent.first, sp_chunk)
        self._observe("prefill_seconds", time.monotonic() - t0)
        if ahead:
            self._chunks_ahead += 1
            if self.obs_registry is not None:
                self.obs_registry.inc("prefill_chunks_ahead_total")
        return sent

    def _pull_first(self, first: jax.Array, sp_counts) -> int:
        """The blocking read of a first token's draw; the pending forwards'
        counts (the chunk's own last) go on ``sp_counts``."""
        first_id = int(np.asarray(first)[0])
        sp_counts.set(**self._pull_moe_counts())
        self._pulled()
        return first_id

    def _land_chunk(self, sent: _SentChunk, finished: List[Completion]) -> bool:
        """What follows a chunk that ended its prompt: the first token read
        (here, for a chunk sent ahead: as late as the round allows, after the
        last round's commit and this one's admission), the prompt's pages
        registered, the slot armed for this round's decode.  False where
        nothing landed: the prompt has chunks to go, or its slot was
        cancelled, expired or retired with the chunk in flight — the result
        is dropped (the device's order keeps the chunk's page writes ahead of
        any later owner's)."""
        slot_idx, slot = sent.slot_idx, sent.slot
        if sent.first is None or self._slots[slot_idx] is not slot:
            return False
        req = slot.request
        if sent.first_id is None:
            self._enqueue()  # the covered stretch ends where the host starts to wait
            with self.tracer.span("pull", uid=req.uid) as sp_pull:
                sent.first_id = self._pull_first(sent.first, sp_pull)
        first_id, L = sent.first_id, len(req.prompt)
        if self.prefix_cache is not None:
            # only pages fully covered by prompt tokens register — the
            # donor's decode writes (positions >= L) never touch them
            self._prefix_register(req.prompt, slot.pages)
        # the device has drained and waits for the decode's turn
        with self.tracer.span("first_token", uid=req.uid):
            slot.decoding = True
            slot.tokens = [first_id]
            slot.pos = L
            slot.t_first = time.monotonic()
            slot.span = self.tracer.start_span(
                "decode", trace_id=self._trace_ids.get(req.uid), uid=req.uid
            )
            self._tokens[slot_idx] = first_id
            self._positions[slot_idx] = L
            self._tables[slot_idx, : len(slot.pages)] = slot.pages
            if slot.draft_pages:
                self._draft_tables[slot_idx, : len(slot.draft_pages)] = slot.draft_pages
            self._emit_token(req.uid, first_id, 0)
            self._finish_if_done(slot_idx, finished)
            self._maybe_migrate(slot_idx)
        return True

    # -- disaggregated handoff (prefill role -> decode peer) --------------------

    def _find_slot(self, uid: int) -> Optional[int]:
        for slot_idx, slot in enumerate(self._slots):
            if slot is not None and slot.request.uid == uid:
                return slot_idx
        return None

    def _maybe_migrate(self, slot_idx: int) -> None:
        """Donor side: a prefill-role scheduler that just completed a prompt
        exports its filled page run and hands ``(record, entries)`` to the
        server's migration sink.  The slot parks as ``migrating`` — out of
        both the prefill and decode sets — until ``migration_commit`` /
        ``migration_abort`` / ``migration_failed`` resolves it.  Any export
        or sink error fails open: the slot resumes decoding locally."""
        if self.role != "prefill" or self.migration_sink is None:
            return
        slot = self._slots[slot_idx]
        if slot is None or slot.migrating or not slot.decoding:
            return  # finished at prefill (eos / max_new_tokens == 1)
        req = slot.request
        n_pages = pages_needed(len(req.prompt), self.engine.page_size)
        try:
            faults.maybe_fail("serve_migrate")
            entries = self.engine.export_page_run(
                self._ensure_pool(), slot.pages[:n_pages]
            )
        except Exception as e:
            logger.warning(f"request {req.uid}: page-run export failed: {e!r}")
            self._count_migration_failure(req.uid, f"export failed: {e}")
            return  # slot keeps decoding locally, untouched
        record = wire.build_migration_record(
            uid=req.uid,
            prompt=req.prompt,
            max_new_tokens=req.max_new_tokens,
            temperature=req.temperature,
            top_p=req.top_p,
            spec=req.spec,
            adapter=req.adapter,
            first_token=slot.tokens[0],
            position=slot.pos,
            token_index=len(slot.tokens),
            n_pages=n_pages,
        )
        # park: the decode row goes back to the null table so this round's
        # (and every later round's) garbage write lands in the null page
        slot.migrating = True
        slot.decoding = False
        self._tokens[slot_idx] = 0
        self._positions[slot_idx] = 0
        self._tables[slot_idx, :] = 0
        ok = False
        try:
            ok = bool(self.migration_sink(record, entries))
        except Exception as e:
            logger.warning(f"request {req.uid}: migration sink failed: {e!r}")
        if not ok:
            self.migration_failed(req.uid, "sink rejected handoff")

    def migration_failed(self, uid: int, detail: Optional[str] = None) -> None:
        """Fail open: the handoff died before the peer relayed any token —
        resume decoding locally from exactly where prefill left off.  The
        client stream never notices (same sampling keys, same token
        indices); the failure is a typed counter + event, not an error."""
        slot_idx = self._find_slot(uid)
        if slot_idx is None:
            return  # cancelled/expired while the transfer was in flight
        slot = self._slots[slot_idx]
        if not slot.migrating:
            return
        slot.migrating = False
        slot.decoding = True
        self._tokens[slot_idx] = slot.tokens[-1]
        self._positions[slot_idx] = slot.pos
        self._tables[slot_idx, : len(slot.pages)] = slot.pages
        self._count_migration_failure(uid, detail)

    def _count_migration_failure(self, uid: int, detail: Optional[str]) -> None:
        self._migration_failures += 1
        logger.warning(
            f"request {uid}: migration failed open to local decode"
            + (f" ({detail})" if detail else "")
        )
        if self.obs_registry is not None:
            self.obs_registry.inc("migration_failures_total")

    def migration_commit(self, uid: int, bytes_sent: int = 0) -> Optional[Completion]:
        """The decode peer accepted the run and the relay delivered the
        peer's finish: retire the donor slot WITHOUT firing the client
        callbacks (the relay already owns that stream) and free its pages."""
        slot_idx = self._find_slot(uid)
        if slot_idx is None:
            return None
        slot = self._slots[slot_idx]
        if not slot.migrating:
            return None
        self._on_token.pop(uid, None)
        self._on_finish.pop(uid, None)
        n_pages = pages_needed(len(slot.request.prompt), self.engine.page_size)
        self._pages_migrated += n_pages
        self._migration_bytes += bytes_sent
        if self.obs_registry is not None:
            self.obs_registry.inc("pages_migrated_total", by=n_pages)
            self.obs_registry.inc("migration_bytes_total", by=bytes_sent)
        return self._retire(slot_idx, "migrated")

    def migration_abort(self, uid: int, detail: Optional[str] = None) -> Optional[Completion]:
        """The peer died AFTER relaying at least one token: the request
        cannot be silently replayed (PR 9 idempotency boundary), so the
        server sends the client a typed error finish and this retires the
        donor slot without firing the (already-detached) callbacks."""
        slot_idx = self._find_slot(uid)
        if slot_idx is None:
            return None
        slot = self._slots[slot_idx]
        if not slot.migrating:
            return None
        self._on_token.pop(uid, None)
        self._on_finish.pop(uid, None)
        self._count_migration_failure(uid, detail or "peer died mid-relay")
        return self._retire(slot_idx, "error", detail or "migration_failed")

    def submit_migrated(
        self,
        record: Dict[str, Any],
        entries: Sequence,
        *,
        on_token: Optional[TokenCallback] = None,
        on_finish: Optional[FinishCallback] = None,
        deadline: Optional[float] = None,
        trace_id: Optional[str] = None,
    ) -> None:
        """Receiver side: install a migrated request straight into a decode
        slot — scatter its page run into freshly allocated pages, arm the
        decode row at the donor's position, and continue sampling with keys
        ``(uid, token_index)`` unchanged, so the drain is token-identical to
        a mixed replica.  Raises on ANY precondition miss (dup uid, no free
        slot, no adapter capacity, pool exhausted, malformed run) — the
        donor maps a raise to fail-open local decode, so rejecting here is
        always safe.  Runs on the model thread, like every mutator."""
        fields = wire.parse_migration_record(record)
        req = Request(
            uid=fields["uid"],
            prompt=fields["prompt"],
            max_new_tokens=fields["max_new_tokens"],
            temperature=fields["temperature"],
            top_p=fields["top_p"],
            spec=fields["spec"],
            adapter=fields["adapter"],
        )
        self.validate_request(req)
        if req.uid in self._deadlines or req.uid in self._on_finish or any(
            r.uid == req.uid for r in self._pending
        ) or any(s is not None and s.request.uid == req.uid for s in self._slots):
            raise ValueError(f"migrated request {req.uid}: uid already in flight")
        L = len(req.prompt)
        n_pages = fields["n_pages"]
        if fields["position"] != L or n_pages != pages_needed(
            L, self.engine.page_size
        ):
            raise ValueError(
                f"migrated request {req.uid}: inconsistent run "
                f"(position {record['position']}, n_pages {n_pages}, prompt {L})"
            )
        slot_idx = next(
            (i for i in range(self.max_batch) if self._slots[i] is None), None
        )
        if slot_idx is None:
            raise RuntimeError(f"migrated request {req.uid}: no free slot")
        adapter_slot = self._acquire_adapter(req)
        if adapter_slot is None:
            raise RuntimeError(f"migrated request {req.uid}: no adapter capacity")
        try:
            need = pages_needed(L + req.max_new_tokens, self.engine.page_size)
            pages = self.allocator.alloc(need)
            if pages is None and self.prefix_cache is not None:
                self.prefix_cache.evict(need)
                pages = self.allocator.alloc(need)
            if pages is None:
                raise RuntimeError(f"migrated request {req.uid}: pool exhausted")
            try:
                self._pool = self.engine.import_page_run(
                    self._ensure_pool(), pages[:n_pages], entries
                )
            except Exception:
                self.allocator.decref(pages)
                raise
        except Exception:
            self._release_adapter(req)
            raise
        first = fields["first_token"]
        now = time.monotonic()
        self._slots[slot_idx] = _PagedSlot(
            request=req,
            pos=L,
            tokens=[first],
            t_admit=now,
            t_first=now,
            deadline=deadline,
            span=self.tracer.start_span("decode", trace_id=trace_id, uid=req.uid),
            pages=pages,
            shared_pages=0,
            prefill_progress=L,
            decoding=True,
            seq=self._admit_seq,
            adapter_slot=adapter_slot,
        )
        self._admit_seq += 1
        if deadline is not None:
            self._deadlines[req.uid] = deadline
        if on_token is not None:
            self._on_token[req.uid] = on_token
        if on_finish is not None:
            self._on_finish[req.uid] = on_finish
        if trace_id is not None:
            self._trace_ids[req.uid] = trace_id
        self._tokens[slot_idx] = first
        self._positions[slot_idx] = L
        self._tables[slot_idx, :] = 0
        self._tables[slot_idx, : len(pages)] = pages
        self._ptables[slot_idx, :] = 0
        self._ptables[slot_idx, : len(pages)] = pages
        self._adapter_row[slot_idx] = adapter_slot
        if self.prefix_cache is not None:
            # the migrated prompt's pages are as shareable as a locally
            # prefilled one's — register them for later local hits
            self._prefix_register(req.prompt, pages)
        self._migrated_inserts += 1
        if self.obs_registry is not None:
            self.obs_registry.inc("migrated_inserts_total")

    def _fetch_prefix(self, req: Request) -> tuple:
        """Fleet prefix-page directory client path: on a local miss, ask the
        directory for the longest cached page-aligned prefix of ``req``'s
        prompt held by a peer, import its pages, register them locally, and
        re-run the local lookup.  Every failure path returns ``([], 0)`` —
        fail open to local prefill."""
        ps = self.engine.page_size
        k_max = (len(req.prompt) - 1) // ps
        if k_max < 1 or self.prefix_cache is None:
            return [], 0
        digests = [
            PrefixCache._digest(req.prompt[: k * ps]).hex()
            for k in range(k_max, 0, -1)
        ]
        try:
            faults.maybe_fail("serve_prefix_fetch")
            hit = self.prefix_fetch(digests)
            if hit is None:
                return [], 0
            n_tokens, entries, nbytes = hit
            n_tokens = int(n_tokens)
            if n_tokens < ps or n_tokens % ps or n_tokens > k_max * ps:
                raise ValueError(f"peer returned unusable prefix ({n_tokens} tokens)")
            n_pages = n_tokens // ps
            pages = self.allocator.alloc(n_pages)
            if pages is None:
                self.prefix_cache.evict(n_pages)
                pages = self.allocator.alloc(n_pages)
            if pages is None:
                return [], 0  # pool pressure: not a failure, just skip
            try:
                self._pool = self.engine.import_page_run(
                    self._ensure_pool(), pages, entries
                )
            except Exception:
                self.allocator.decref(pages)
                raise
            self._prefix_register(req.prompt[:n_tokens], pages)
            # the cache's own refs keep the run alive; drop the alloc ref and
            # let the re-lookup incref for this request like any local hit
            self.allocator.decref(pages)
            self._prefix_fetches += 1
            self._migration_bytes += int(nbytes)
            if self.obs_registry is not None:
                self.obs_registry.inc("prefix_fetch_total")
                self.obs_registry.inc("migration_bytes_total", by=int(nbytes))
            return self._prefix_lookup(req.prompt)
        except Exception as e:
            logger.warning(f"request {req.uid}: prefix fetch failed: {e!r}")
            self._prefix_fetch_failures += 1
            if self.obs_registry is not None:
                self.obs_registry.inc("prefix_fetch_failures_total")
            return [], 0

    def _prefix_lookup(self, prompt: Sequence[int]) -> tuple:
        """``prefix_cache.lookup`` under the ``prefix_lookup`` span, with what
        it hashed (the cache counts the tokens; the span takes the difference)."""
        cache = self.prefix_cache
        hashed = cache.hashed_tokens
        with self.tracer.span("prefix_lookup", prompt_tokens=len(prompt)) as sp:
            pages, n_tokens = cache.lookup(prompt)
            sp.set(hashed_tokens=cache.hashed_tokens - hashed, hit_tokens=n_tokens)
        return pages, n_tokens

    def _prefix_register(self, prompt: Sequence[int], pages: Sequence[int]) -> None:
        """``prefix_cache.register`` under the ``prefix_register`` span."""
        cache = self.prefix_cache
        hashed = cache.hashed_tokens
        with self.tracer.span("prefix_register", prompt_tokens=len(prompt)) as sp:
            created = cache.register(list(prompt), pages)
            sp.set(hashed_tokens=cache.hashed_tokens - hashed, created=created)

    # -- speculative draft / verify --------------------------------------------

    def _ngram_draft(self, ctx: List[int], k: int) -> List[int]:
        """Prompt-lookup drafting: match the longest context suffix
        (n-gram, ``n <= _NGRAM_MAX``) against an earlier occurrence in the
        row's own prompt+generated tokens and propose the tokens that
        followed it (most recent occurrence wins).  Free — no second model,
        no device work — and effective exactly when generation repeats its
        context, the regime where speculation pays."""
        if k <= 0 or len(ctx) < 2:
            return []
        for n in range(min(self._NGRAM_MAX, len(ctx) - 1), 0, -1):
            pattern = ctx[-n:]
            for i in range(len(ctx) - n - 1, -1, -1):
                if ctx[i : i + n] == pattern:
                    return ctx[i + n : i + n + k]
        return []

    def _draft_pass(self) -> Dict[int, List[int]]:
        """Draft up to ``spec_k`` tokens per decoding row.  A row only
        drafts within its remaining budget minus one (the round always
        commits at least one token), so every window write — accepted or
        rejected — stays inside the worst-case admission allocation and
        rollback never touches the allocator."""
        drafts: Dict[int, List[int]] = {}
        spec_k = self.engine.spec_k
        for slot_idx, slot in enumerate(self._slots):
            if slot is None or not slot.decoding or not slot.request.spec:
                continue
            k = min(spec_k, slot.request.max_new_tokens - len(slot.tokens) - 1)
            if k <= 0:
                continue
            d = self._ngram_draft(list(slot.request.prompt) + slot.tokens, k)
            if d:
                drafts[slot_idx] = d
        return drafts

    def _model_draft_pass(self) -> Dict[int, List[int]]:
        """spec="model": the draft model proposes up to ``spec_k`` tokens per
        decoding row by running k batched ``(batch, 1)`` autoregressive decode
        steps over its own page run, chaining greedy (argmax) proposals on
        device and pulling the whole proposal matrix to the host once at the
        end.  Rows past their own draft budget go null mid-loop (all-null
        table, pos 0) so their garbage writes land in the null page.  The
        same budget rule as the ngram drafter applies (remaining minus one),
        so the verify window never writes past the admission allocation."""
        spec_k = self.engine.spec_k
        B = self.max_batch
        ks = np.zeros(B, np.int32)
        eligible: List[int] = []
        for slot_idx, slot in enumerate(self._slots):
            if slot is None or not slot.decoding or not slot.request.spec:
                continue
            k = min(spec_k, slot.request.max_new_tokens - len(slot.tokens) - 1)
            if k <= 0:
                continue
            eligible.append(slot_idx)
            ks[slot_idx] = k
        if not eligible:
            return {}
        k_max = int(ks.max())
        cur = self._tokens[:, None]
        proposals = []
        self._enqueue()
        for step in range(k_max):
            live = ks > step
            positions = np.where(live, self._positions + step, 0).astype(np.int32)
            tables = np.where(live[:, None], self._draft_tables, 0).astype(np.int32)
            logits, self._pool = self.engine.draft_decode_paged(
                self._ensure_pool(), cur, positions[:, None], tables
            )
            self._count_dispatch(B, int(live.sum()))
            cur = jnp.argmax(logits, axis=-1).astype(jnp.int32).reshape(-1, 1)
            proposals.append(cur)
        stacked = np.asarray(jnp.concatenate(proposals, axis=1))  # one host pull
        self._pulled()
        return {
            i: [int(t) for t in stacked[i, : int(ks[i])]] for i in eligible
        }

    def _verify_dispatch(self, drafts: Dict[int, List[int]]) -> tuple:
        """Enqueue one ``(batch, spec_k+1)`` verify forward over every
        decoding row and the accept draws over its logits; returns
        ``(accept, alt, draft_mat, k_eff)``, the first two still on the
        device, for the round's pull and its accept walk
        (``_commit_spec_walk``).  Window row 0 carries the pending token;
        rows ``1..k`` carry the drafts at consecutive positions.  Padding
        rows (free / prefilling / short drafts) write through the trailing
        null column of the ``W+1``-wide tables at ``pos >= cache_size``, so
        no live page is ever touched."""
        spec_k = self.engine.spec_k
        S = spec_k + 1
        B = self.max_batch
        W = self.engine.block_table_width
        null_pos = self.engine.cache_size  # clips into the null column
        tokens = np.zeros((B, S), np.int32)
        positions = np.full((B, S), null_pos, np.int32)
        tables = np.zeros((B, W + 1), np.int32)
        draft_mat = np.zeros((B, spec_k), np.int32)
        k_eff = np.zeros(B, np.int32)
        uids = np.zeros(B, np.int32)
        starts = np.zeros(B, np.int32)
        temps = np.zeros(B, np.float32)
        top_ps = np.ones(B, np.float32)
        offsets = np.arange(S, dtype=np.int32)
        for slot_idx, slot in enumerate(self._slots):
            if slot is None or not slot.decoding:
                continue
            d = drafts.get(slot_idx, [])
            tokens[slot_idx, 0] = self._tokens[slot_idx]
            tokens[slot_idx, 1 : 1 + len(d)] = d
            positions[slot_idx] = self._positions[slot_idx] + offsets
            tables[slot_idx, :W] = self._tables[slot_idx]
            draft_mat[slot_idx, : len(d)] = d
            k_eff[slot_idx] = len(d)
            uids[slot_idx] = slot.request.uid
            starts[slot_idx] = len(slot.tokens)
            temps[slot_idx] = slot.request.temperature
            top_ps[slot_idx] = slot.request.top_p
        n_dec = self._n_decoding()
        logits, self._pool = self.engine.verify_paged(
            self._ensure_pool(), tokens, positions, tables,
            adapter_idx=self._adapter_row,
        )
        self._count_dispatch(B * S, n_dec + int(k_eff.sum()))
        accept, alt = self._spec_sample(
            logits,
            jnp.asarray(draft_mat),
            self.key,
            jnp.asarray(uids),
            jnp.asarray(starts),
            jnp.asarray(k_eff),
            temperature=jnp.asarray(temps),
            top_k=self.top_k,
            top_p=jnp.asarray(top_ps),
        )
        return accept, alt, draft_mat, k_eff

    def _commit_spec_walk(
        self,
        accept: np.ndarray,
        alt: np.ndarray,
        draft_mat: np.ndarray,
        k_eff: np.ndarray,
        eligible: set,
        finished: List[Completion],
    ) -> int:
        """The host-side accept walk shared by the sequential verify round
        and the packed step: for each eligible row commit the longest
        accepted draft prefix plus one corrective token through the normal
        emit/finish flow, stopping at EOS.  ``eligible`` is the set of slot
        indices that actually rode the verify window (the packed step must
        exclude slots it armed for decode *after* the dispatch).  Returns
        the number of tokens committed."""
        drafted = accepted = committed = 0
        for slot_idx in sorted(eligible):
            slot = self._slots[slot_idx]
            if slot is None or not slot.decoding:
                continue
            k = int(k_eff[slot_idx])
            a = 0
            while a < k and accept[slot_idx, a]:
                a += 1
            drafted += k
            accepted += a
            commits = [int(t) for t in draft_mat[slot_idx, :a]]
            commits.append(int(alt[slot_idx, a]))
            req = slot.request
            for tok in commits:
                slot.tokens.append(tok)
                slot.pos += 1
                self._tokens[slot_idx] = tok
                self._positions[slot_idx] = slot.pos
                self._emit_token(req.uid, tok, len(slot.tokens) - 1)
                committed += 1
                self._finish_if_done(slot_idx, finished)
                if self._slots[slot_idx] is None:
                    break  # EOS / budget inside the window: drop the rest
        self._spec_drafted += drafted
        self._spec_accepted += accepted
        if self.obs_registry is not None and drafted:
            self.obs_registry.inc("spec_drafted_total", by=drafted)
            self.obs_registry.inc("spec_accepted_total", by=accepted)
        return committed

    # -- the budgeted round ----------------------------------------------------

    def step(self) -> List[Completion]:
        """One budgeted round: expire deadlines, admit (page accounting
        only), at most one prefill chunk, then one paged decode over every
        decoding slot.  Returns the requests that finished during it.
        ``packed=True`` replaces the whole round body with the single-
        dispatch packed step (``_step_packed``).

        The round is split into spans where the device waits
        (docs/observability.md, "The serving round"): ``round`` holds
        ``admit`` (``prefix_lookup`` inside it), ``prefill_chunk`` (where the
        last round's decode sent none ahead; ``pull`` alone where the one it
        sent ended a prompt), after a prompt's last chunk
        ``prefix_register`` and ``first_token``, ``decode_prep``,
        ``decode_step`` (``dispatch`` up to the enqueue, with the rows' draws
        as ``sample`` inside it, the next round's ``prefill_chunk`` where a
        slot is prefilling, ``pull`` for the blocking read), ``commit`` and
        ``round_metrics``; a step that dispatched nothing leaves none.  What
        passes between a pull's return and the next enqueue is the round's
        ``host_gap_ms``, or its ``covered_gap_ms`` where a chunk sent ahead
        was queued behind the pull."""
        if self._packed:
            return self._step_packed()
        finished: List[Completion] = []
        t_step = time.monotonic()
        d0 = self._dispatch_total
        with self.tracer.span("round", round=self._round_total) as sp_round:
            self._admit_round(finished)
            # the chunk the last decode sent ahead is this round's: no other
            # runs, unless no row decodes (a pure-prefill round has no decode
            # to send its one chunk behind)
            ahead, self._ahead = self._ahead, None
            landed = ahead is not None and self._land_chunk(ahead, finished)
            if ahead is None or not self._n_decoding():
                self._prefill_pass(finished)
            admit_s = time.monotonic() - t_step
            n_decoding = self._n_decoding()
            if n_decoding == 0:
                if self._dispatch_total > d0:
                    self._count_round()  # pure-prefill round still dispatched
                    self._admit_time_s += admit_s  # a 100%-stall round
                    self._close_round(sp_round, 0, d0)
                elif landed:
                    # only a first token landed, and ended its request: its
                    # spans keep their parent, no reader counts the round
                    self._close_round(sp_round, 0, d0)
                else:
                    sp_round.drop()
                    self.drop_host_gap()
                    if any(s is not None and s.migrating for s in self._slots):
                        time.sleep(0.001)  # only parked handoffs: don't hot-spin
                return finished  # pure-prefill round (or idle)

            t_decode = time.monotonic()
            with self.tracer.span("decode_prep"):
                if self._spec == "ngram":
                    drafts = self._draft_pass()
                elif self._spec == "model":
                    drafts = self._model_draft_pass()
                else:
                    drafts = {}
                n_drafted = sum(len(d) for d in drafts.values())
                rode = set(
                    i for i, s in enumerate(self._slots) if s is not None and s.decoding
                )
                reads = self._decode_reads()
            with self.tracer.span(
                "decode_step",
                step=self._step_count,
                active_slots=n_decoding,
                spec_drafted=n_drafted,
                **reads,
            ) as sp_decode:
                self._enqueue()
                with self.tracer.span("dispatch"):
                    if drafts:
                        # draft→verify→accept: one verify window per row
                        accept, alt, draft_mat, k_eff = self._verify_dispatch(drafts)
                    else:
                        # no row drafted (spec off, or nothing to look up):
                        # the plain warmed (batch, 1) decode shape
                        logits, self._pool = self.engine.decode_paged(
                            self._ensure_pool(),
                            self._tokens[:, None],
                            self._positions[:, None],
                            self._tables,
                            adapter_idx=self._adapter_row,
                        )
                        self._count_dispatch(self.max_batch, n_decoding)
                        masked = [
                            s if (s is not None and s.decoding) else None
                            for s in self._slots
                        ]
                        drawn = self._sample_rows(logits, masked)
                    self._step_count += 1
                # the next round's chunk needs nothing this pull brings: it
                # goes behind the decode now, and the pull reads (and
                # converts the counts of) only what lies before it
                behind = len(self._moe_pending)
                self._ahead = self._send_chunk(ahead=True)
                with self.tracer.span("pull"):
                    if drafts:
                        accept, alt = np.asarray(accept), np.asarray(alt)
                    else:
                        next_tokens = np.asarray(drawn).tolist()
                    sp_decode.set(**self._pull_moe_counts(behind))
                    self._pulled(covered=self._ahead is not None)
            decode_s = time.monotonic() - t_decode
            self._observe("decode_step_seconds", decode_s)
            self._count_round()
            with self.tracer.span("commit") as sp_commit:
                if drafts:
                    committed = self._commit_spec_walk(
                        accept, alt, draft_mat, k_eff, rode, finished
                    )
                else:
                    committed = self._commit_tokens(next_tokens, sorted(rode), finished)
                sp_commit.set(tokens=committed)
            with self.tracer.span("round_metrics"):
                self._round_metrics(admit_s, decode_s, n_decoding)
            self._close_round(sp_round, n_decoding, d0)
        return finished

    # -- the round's pieces, shared by the sequential and the packed step ------

    def _admit_round(self, finished: List[Completion]) -> None:
        """The ``admit`` span: deadlines, then admission (host work only)."""
        with self.tracer.span("admit") as sp:
            seq0 = self._admit_seq
            self._expire_deadlines(finished)
            self._admit_pass(finished)
            sp.set(admitted=self._admit_seq - seq0)
            if not any(s is not None and not s.migrating for s in self._slots):
                sp.drop()  # nothing to run: the round will leave no span either

    def _decode_reads(self) -> Dict[str, float]:
        """What a decode over the live tokens must read, whatever kernel
        reads it (the ``decode_step`` span's attributes): every decoding row
        attends positions ``0..pos``, which are ``kv_bytes`` bytes of K/V on
        ``live_pages`` entries of the block tables; the other entries of the
        ``max_batch x table width`` tables are what a kernel may skip."""
        positions = [s.pos for s in self._slots if s is not None and s.decoding]
        ps = self.engine.page_size
        self._decode_live_pages = sum(p // ps + 1 for p in positions)
        if self._ring_spec is None:
            return {
                "kv_bytes": sum(p + 1 for p in positions) * self._kv_bytes_per_token,
                "live_pages": self._decode_live_pages,
            }
        # two cache kinds: a window layer reads its last ``window`` tokens,
        # however long the request
        by_kind = [sum(c.read_bytes(p) for p in positions) for c in (self._paged_spec, self._ring_spec)]
        # rows whose ring has wrapped: their window layers' walk is the whole
        # window, modulo the ring, whatever the position
        self._ring_wrapped_rows = sum(p >= self._ring_spec.window for p in positions)
        return {
            "kv_bytes": sum(by_kind),
            "kv_bytes_global": by_kind[0],
            "kv_bytes_window": by_kind[1],
            "rows_past_window": self._ring_wrapped_rows,
            "live_pages": self._decode_live_pages,
        }

    def _pull_moe_counts(self, first: Optional[int] = None) -> Dict[str, int]:
        """Pull what the forwards since the last pull counted (the arrays are
        on the device behind results this round has already waited for),
        add them to the counters, and return the newest forward's as span
        attributes; nothing for a model without routed experts.  ``first``:
        only the first so many pending forwards' — those of a chunk sent
        ahead lie behind what was waited for, and wait for the next pull."""
        n = len(self._moe_pending) if first is None else first
        pending, self._moe_pending = self._moe_pending[:n], self._moe_pending[n:]
        if not pending:
            return {}
        pulled = [(routed, np.asarray(counts)) for routed, counts in pending]  # noqa: RTL204 - in the round's pull
        if self.obs_registry is not None:
            self.obs_registry.inc("moe_assignments_total", by=sum(r for r, _ in pulled))
            self.obs_registry.inc("moe_assignments_local_total", by=int(sum(c[0] for _, c in pulled)))
            self.obs_registry.inc("moe_experts_hit_total", by=int(sum(c[1] for _, c in pulled)))
        local, hit = (int(v) for v in pulled[-1][1])
        return {"moe_assignments_local": local, "expert_bytes": hit * self._expert_bytes}

    def _commit_tokens(
        self, next_tokens: List[int], rows: List[int], finished: List[Completion]
    ) -> int:
        """Append each decoded row's token, stream it, retire what is done;
        returns the number of tokens committed."""
        committed = 0
        for slot_idx in rows:
            slot = self._slots[slot_idx]
            if slot is None:
                continue  # retired by another row's token callback
            committed += 1
            tok = next_tokens[slot_idx]
            slot.tokens.append(tok)
            slot.pos += 1
            self._tokens[slot_idx] = tok
            self._positions[slot_idx] = slot.pos
            self._emit_token(slot.request.uid, tok, len(slot.tokens) - 1)
            self._finish_if_done(slot_idx, finished)
        return committed

    def _n_decoding(self) -> int:
        return sum(s is not None and s.decoding for s in self._slots)

    def _close_round(self, sp_round, n_decoding: int, d0: int) -> None:
        gap_s, self._host_gap_s = self._host_gap_s, 0.0
        covered_s, self._covered_gap_s = self._covered_gap_s, 0.0
        self._observe("host_gap_seconds", gap_s)
        sp_round.set(
            decoding=n_decoding,
            prefilling=sum(
                s is not None and not s.decoding and not s.migrating
                for s in self._slots
            ),
            dispatches=self._dispatch_total - d0,
            host_gap_ms=1e3 * gap_s,
            covered_gap_ms=1e3 * covered_s,
            chunk_ahead=1 if self._ahead is not None else 0,
        )

    # -- the host gap -----------------------------------------------------------
    # A blocking pull behind which nothing was queued returns only once the
    # device has drained, so from there to the next enqueue the device has
    # nothing queued and waits for the host.  A decode's pull with the next
    # round's chunk queued behind it (``covered``) leaves the device at work:
    # what passes from there to the next enqueue, or to the read of that
    # chunk's first token, is counted apart, as covered.  The stamp outlives
    # the round span: the gap before a round's first enqueue (the last
    # round's commit and metrics, the server's loop, this round's admission)
    # is this round's.

    def _pulled(self, covered: bool = False) -> None:
        self._pull_stamp = self.tracer.clock()
        self._pull_covered = covered

    def _enqueue(self) -> None:
        """Device work is about to be queued (or a chunk sent ahead read):
        count what has passed since the last pull returned, if anything is
        counted from."""
        if self._pull_stamp is not None:
            passed = self.tracer.clock() - self._pull_stamp
            if self._pull_covered:
                self._covered_gap_s += passed
            else:
                self._host_gap_s += passed
            self._pull_stamp = None

    def drop_host_gap(self) -> None:
        """A step that dispatched nothing, or the server's loop waiting for
        a request: the wait is not the host's, and is not counted."""
        self._pull_stamp = None

    # -- dispatch accounting ----------------------------------------------------

    def _count_dispatch(self, tokens: int, real: int) -> None:
        """One model dispatch of ``tokens`` window positions, ``real`` of
        which carried live work (the rest is shape padding).  A model with
        routed experts routes every position, padding too: the dispatch's
        assignments and the device's count of the local ones wait for the
        next pull (:meth:`_pull_moe_counts`)."""
        if self.engine.moe_counts is not None:
            self._moe_pending.append((tokens * self._moe_fanout, self.engine.moe_counts))
        self._dispatch_total += 1
        self._dispatch_tokens += tokens
        self._dispatch_tokens_real += real
        if self.obs_registry is not None:
            self.obs_registry.inc("model_dispatches_total")
            self.obs_registry.inc("dispatch_tokens_total", by=tokens)
            self.obs_registry.inc("dispatch_tokens_real_total", by=real)

    def _count_round(self) -> None:
        self._round_total += 1
        if self.obs_registry is not None:
            self.obs_registry.inc("sched_rounds_total")

    def publish_constants(self) -> None:
        """Beside the weights' bytes: the gauges that are constants of the
        pool, and every counter of the round at 0, so that ``/metrics`` has
        the series (and a scraper's deltas a base) before the first count.
        A round publishes only what a round can change."""
        super().publish_constants()
        registry = self.obs_registry
        if registry is None:
            return
        registry.set_gauge("kv_cache_bytes", self._kv_cache_bytes)
        registry.set_gauge("kv_bytes_per_token", self._kv_bytes_per_token)
        if self._ring_spec is not None:
            for kind, nbytes in self._kv_cache_bytes_by_kind.items():
                registry.set_gauge(f"kv_cache_bytes_{kind}", nbytes)
            registry.set_gauge("window_ring_pages", self._ring_spec.table_width)
        registry.materialize_histogram("host_gap_seconds")
        registry.inc("model_dispatches_total", by=0)
        registry.inc("prefill_chunks_ahead_total", by=0)
        registry.inc("sched_rounds_total", by=0)
        registry.inc("dispatch_tokens_total", by=0)
        registry.inc("dispatch_tokens_real_total", by=0)
        registry.inc("pages_migrated_total", by=0)
        registry.inc("migration_bytes_total", by=0)
        registry.inc("migration_failures_total", by=0)
        registry.inc("migrated_inserts_total", by=0)
        registry.inc("prefix_fetch_total", by=0)
        registry.inc("prefix_fetch_failures_total", by=0)
        if self._moe_fanout:
            registry.inc("moe_assignments_total", by=0)
            registry.inc("moe_assignments_local_total", by=0)
            registry.inc("moe_experts_hit_total", by=0)
        if self._spec != "off":
            registry.set_gauge("spec_mode_model", 1.0 if self._spec == "model" else 0.0)
            registry.inc("spec_drafted_total", by=0)
            registry.inc("spec_accepted_total", by=0)

    def _round_metrics(self, admit_s: float, decode_s: float, n_decoding: int) -> None:
        """Publish the round's gauges and metrics.jsonl record — shared by
        the sequential and packed step bodies so both expose an identical
        telemetry surface.  What no round changes, :meth:`publish_constants` has set."""
        batch_fill = n_decoding / self.max_batch
        stall_share = admit_s / max(admit_s + decode_s, 1e-9)
        self._admit_time_s += admit_s
        self._decode_time_s += decode_s
        pad_share = self._pad_tokens / max(self._pad_tokens + self._prefill_tokens, 1)
        hit_rate = self.prefix_cache.hit_rate if self.prefix_cache is not None else 0.0
        dispatches_per_round = self._dispatch_total / max(self._round_total, 1)
        tokens_per_dispatch = self._dispatch_tokens / max(self._dispatch_total, 1)
        token_utilization = self._dispatch_tokens_real / max(self._dispatch_tokens, 1)
        live_page_share = self._decode_live_pages / self._tables.size
        if self.obs_registry is not None:
            self.obs_registry.set_gauge("batch_fill", batch_fill)
            self.obs_registry.set_gauge("prefill_stall_share", stall_share)
            self.obs_registry.set_gauge("kv_pages_used", self.allocator.used_pages)
            self.obs_registry.set_gauge("kv_pages_free", self.allocator.free_pages)
            self.obs_registry.set_gauge("prefix_cache_hit_rate", hit_rate)
            self.obs_registry.set_gauge("prefill_pad_share", pad_share)
            if self._ring_spec is not None:
                self.obs_registry.set_gauge("ring_wrapped_rows", self._ring_wrapped_rows)
            self.obs_registry.set_gauge("decode_live_page_share", live_page_share)
            self.obs_registry.set_gauge("dispatches_per_round", dispatches_per_round)
            self.obs_registry.set_gauge("tokens_per_dispatch", tokens_per_dispatch)
            self.obs_registry.set_gauge("packed_token_utilization", token_utilization)
            if self._spec != "off":
                self.obs_registry.set_gauge(
                    "spec_accept_rate",
                    self._spec_accepted / max(self._spec_drafted, 1),
                )
        record = None
        if self.metrics is not None:
            watcher = getattr(self.engine, "compile_watcher", None)
            record = {
                "serve/decode_step": self._step_count,
                "serve/queue_depth": len(self._pending),
                "serve/active_slots": self.active_slots,
                "serve/batch_fill": round(batch_fill, 4),
                "serve/prefill_stall_s": round(admit_s, 6),
                "serve/prefill_stall_share": round(stall_share, 4),
                "serve/kv_pages_used": self.allocator.used_pages,
                "serve/kv_pages_free": self.allocator.free_pages,
                "serve/prefix_cache_hit_rate": round(hit_rate, 4),
                "serve/prefill_pad_share": round(pad_share, 4),
                "serve/kv_cache_bytes": self._kv_cache_bytes,
                **(
                    {
                        "serve/kv_cache_bytes_paged": self._kv_cache_bytes_by_kind[PAGED],
                        "serve/kv_cache_bytes_ring": self._kv_cache_bytes_by_kind[RING],
                        "serve/window_ring_pages": self._ring_spec.table_width,
                        "serve/ring_wrapped_rows": self._ring_wrapped_rows,
                    }
                    if self._ring_spec is not None
                    else {}
                ),
                "serve/kv_bytes_per_token": round(self._kv_bytes_per_token, 4),
                "serve/decode_live_page_share": round(live_page_share, 4),
                "serve/dispatches_per_round": round(dispatches_per_round, 4),
                "serve/tokens_per_dispatch": round(tokens_per_dispatch, 4),
                "serve/packed_token_utilization": round(token_utilization, 4),
                "compile/steady_state_retraces": (
                    watcher.steady_state_retraces if watcher is not None else 0
                ),
            }
            if self._spec != "off":
                record["serve/spec_drafted_total"] = self._spec_drafted
                record["serve/spec_accepted_total"] = self._spec_accepted
                record["serve/spec_accept_rate"] = round(
                    self._spec_accepted / max(self._spec_drafted, 1), 4
                )
                record["serve/spec_mode_model"] = (
                    1 if self._spec == "model" else 0
                )
        self._adapter_gauges(record)
        if record is not None:
            self.metrics.log(record)

    # -- the packed single-dispatch round ---------------------------------------

    def _step_packed(self) -> List[Completion]:
        """Sarathi-style token-budget round in ONE model dispatch: every
        decoding row's window first (1 token plain, ``spec_k+1`` when any
        row drafted — mirroring the sequential round's branch structure),
        then oldest-first prefill tokens from as many slots as the budget
        admits, padded up to the smallest warmed bucket.  Each packed token
        routes through its own slot's block table (``row_map``), so the
        forward is exactly the sequential dispatches fused.  Sampling reuses
        the sequential path's calls verbatim — same ``(uid, token_index)``
        keys, same scalar-vs-stacked key structure — so the drain is
        token-identical to the unpacked scheduler."""
        finished: List[Completion] = []
        with self.tracer.span("round", round=self._round_total) as sp_round:
            if not self._packed_round(sp_round, finished):
                sp_round.drop()
                self.drop_host_gap()
        return finished

    def _packed_round(self, sp_round, finished: List[Completion]) -> bool:
        """The packed round's body under its ``round`` span (the same span
        names as the sequential ``step``); False when nothing was dispatched."""
        t_step = time.monotonic()
        d0 = self._dispatch_total
        self._admit_round(finished)
        admit_s = time.monotonic() - t_step
        if not any(s is not None for s in self._slots):
            return False

        t_decode = time.monotonic()
        engine = self.engine
        B = self.max_batch
        null_pos = engine.cache_size
        spec_k = engine.spec_k

        # the draft pass and the window's assembly: host work before the enqueue
        with self.tracer.span("decode_prep") as sp_prep:
            drafts = self._draft_pass() if self._spec == "ngram" else {}
            spec_mode = bool(drafts)
            S = spec_k + 1 if spec_mode else 1

            ids: List[int] = []
            poss: List[int] = []
            rows: List[int] = []
            adap: List[int] = []
            slot_off: Dict[int, int] = {}  # decoding slot -> its window's offset

            # decode/verify windows first — the budget never throttles decode
            # (ctor floor check); k_eff=0 rows ride the full window in spec mode,
            # mirroring _verify_dispatch
            draft_mat = np.zeros((B, max(spec_k, 1)), np.int32)
            k_eff = np.zeros(B, np.int32)
            uids = np.zeros(B, np.int32)
            starts = np.zeros(B, np.int32)
            temps = np.zeros(B, np.float32)
            top_ps = np.ones(B, np.float32)
            for slot_idx, slot in enumerate(self._slots):
                if slot is None or not slot.decoding:
                    continue
                slot_off[slot_idx] = len(ids)
                d = drafts.get(slot_idx, [])
                window = [int(self._tokens[slot_idx])] + [int(t) for t in d]
                window += [0] * (S - len(window))
                ids.extend(window)
                poss.extend(int(self._positions[slot_idx]) + j for j in range(S))
                rows.extend([slot_idx] * S)
                adap.extend([slot.adapter_slot] * S)
                draft_mat[slot_idx, : len(d)] = d
                k_eff[slot_idx] = len(d)
                uids[slot_idx] = slot.request.uid
                starts[slot_idx] = len(slot.tokens)
                temps[slot_idx] = slot.request.temperature
                top_ps[slot_idx] = slot.request.top_p
            n_decoding = len(slot_off)

            # oldest-first prefill from MULTIPLE slots into the leftover budget;
            # write-then-attend makes several chunks of one prompt inside one
            # dispatch correct, so a slot may clear its whole backlog here
            budget_left = engine.token_budget - len(ids)
            prefill_spans: List[tuple] = []  # (slot_idx, start, n, packed offset)
            for _, slot_idx in sorted(
                (s.seq, i)
                for i, s in enumerate(self._slots)
                if s is not None and not s.decoding and not s.migrating
            ):
                if budget_left <= 0:
                    break
                slot = self._slots[slot_idx]
                req = slot.request
                start = slot.prefill_progress
                n = min(len(req.prompt) - start, budget_left)
                if n <= 0:
                    continue
                prefill_spans.append((slot_idx, start, n, len(ids)))
                ids.extend(int(t) for t in req.prompt[start : start + n])
                poss.extend(range(start, start + n))
                rows.extend([slot_idx] * n)
                adap.extend([slot.adapter_slot] * n)
                budget_left -= n

            n_real = len(ids)
            if n_real == 0:
                sp_prep.drop()
                if any(s is not None and s.migrating for s in self._slots):
                    time.sleep(0.001)  # only parked handoffs: don't hot-spin
                return False  # nothing decodable and nothing left to prefill
            bucket = next(b for b in engine.packed_buckets() if b >= n_real)
            pad = bucket - n_real
            ids.extend([0] * pad)
            poss.extend([null_pos] * pad)  # clips into the null page
            rows.extend([B] * pad)  # the all-null pad row of _ptables
            adap.extend([0] * pad)
            self._pad_tokens += pad
            self._prefill_tokens += sum(n for _, _, n, _ in prefill_spans)

            # slots whose prompt ends inside this dispatch: their first token is
            # drawn with the same per-slot scalar call and (uid, 0) key as the
            # sequential chunk path, so first tokens match exactly
            ending = [
                (slot_idx, off + n - 1)
                for slot_idx, start, n, off in prefill_spans
                if start + n >= len(self._slots[slot_idx].request.prompt)
            ]
            reads = self._decode_reads()
        with self.tracer.span(
            "decode_step",
            step=self._step_count,
            active_slots=n_decoding,
            spec_drafted=int(k_eff.sum()),
            packed_tokens=bucket,
            **reads,
        ):
            self._enqueue()
            with self.tracer.span("dispatch"):
                logits, self._pool = engine.step_paged(
                    self._ensure_pool(),
                    np.asarray(ids, np.int32)[None, :],
                    np.asarray(poss, np.int32)[None, :],
                    self._ptables,
                    np.asarray(rows, np.int32),
                    adapter_idx=np.asarray(adap, np.int32),
                )
                self._step_count += 1
                # decode rows: gather each window's logits from its packed
                # offsets and reuse the sequential sampling calls unchanged
                drawn = None
                if n_decoding and spec_mode:
                    win_idx = np.zeros(B * S, np.int32)
                    for slot_idx, off in slot_off.items():
                        win_idx[slot_idx * S : (slot_idx + 1) * S] = off + np.arange(S)
                    win = jnp.take(logits[0], jnp.asarray(win_idx), axis=0).reshape(
                        B, S, logits.shape[-1]
                    )
                    drawn = self._spec_sample(
                        win,
                        jnp.asarray(draft_mat),
                        self.key,
                        jnp.asarray(uids),
                        jnp.asarray(starts),
                        jnp.asarray(k_eff),
                        temperature=jnp.asarray(temps),
                        top_k=self.top_k,
                        top_p=jnp.asarray(top_ps),
                    )
                elif n_decoding:
                    sample_idx = np.zeros(B, np.int32)
                    for slot_idx, off in slot_off.items():
                        sample_idx[slot_idx] = off
                    gathered = jnp.take(logits[0], jnp.asarray(sample_idx), axis=0)
                    masked = [
                        s if i in slot_off else None
                        for i, s in enumerate(self._slots)
                    ]
                    drawn = self._sample_rows(gathered, masked)
                firsts = [
                    self._sample_first(logits[:, at, :], self._slots[slot_idx].request)
                    for slot_idx, at in ending
                ]
            with self.tracer.span("pull"):
                if drawn is not None and spec_mode:
                    accept, alt = np.asarray(drawn[0]), np.asarray(drawn[1])
                elif drawn is not None:
                    next_tokens = np.asarray(drawn).tolist()
                first_ids = [int(np.asarray(first)[0]) for first in firsts]
                self._pulled()
        decode_s = time.monotonic() - t_decode
        self._observe("decode_step_seconds", decode_s)
        # dispatch and round tick together: a concurrent /healthz read
        # between the engine call and here must never see the packed
        # invariant (dispatches == rounds) transiently violated
        self._count_dispatch(bucket, n_real)
        self._count_round()

        with self.tracer.span("commit") as sp_commit:
            # decode rows first, before any slot armed this round joins the
            # decoding set
            committed = 0
            if drawn is not None and spec_mode:
                committed = self._commit_spec_walk(
                    accept, alt, draft_mat, k_eff, set(slot_off), finished
                )
            elif drawn is not None:
                committed = self._commit_tokens(next_tokens, sorted(slot_off), finished)
            for slot_idx, start, n, _ in prefill_spans:
                if self._slots[slot_idx] is not None:
                    self._slots[slot_idx].prefill_progress = start + n
            # prefill completions: the slot joins the decode set next round
            for (slot_idx, _), first_id in zip(ending, first_ids):
                slot = self._slots[slot_idx]
                if slot is None:
                    continue
                req = slot.request
                if self.prefix_cache is not None:
                    self._prefix_register(req.prompt, slot.pages)
                slot.decoding = True
                slot.tokens = [first_id]
                slot.pos = len(req.prompt)
                slot.t_first = time.monotonic()
                slot.span = self.tracer.start_span(
                    "decode", trace_id=self._trace_ids.get(req.uid), uid=req.uid
                )
                self._tokens[slot_idx] = first_id
                self._positions[slot_idx] = slot.pos
                self._tables[slot_idx, : len(slot.pages)] = slot.pages
                self._emit_token(req.uid, first_id, 0)
                committed += 1
                self._finish_if_done(slot_idx, finished)
                self._maybe_migrate(slot_idx)
            sp_commit.set(tokens=committed)
        with self.tracer.span("round_metrics"):
            self._round_metrics(admit_s, decode_s, n_decoding)
        self._close_round(sp_round, n_decoding, d0)
        return True

    # -- retirement (page bookkeeping) ----------------------------------------

    def _retire(
        self, slot_idx: int, reason: str, detail: Optional[str] = None
    ) -> Completion:
        slot = self._slots[slot_idx]
        completion = super()._retire(slot_idx, reason, detail)
        if slot.pages:
            # one decref per page: fresh pages drop their alloc ref, shared
            # pages drop this request's lookup ref (the prefix cache's own
            # refs keep registered pages alive for the next hit)
            self.allocator.decref(slot.pages)
            slot.pages = []
        if slot.draft_pages:
            self.allocator.decref(slot.draft_pages)
            slot.draft_pages = []
        self._tables[slot_idx, :] = 0
        self._draft_tables[slot_idx, :] = 0
        self._ptables[slot_idx, :] = 0
        self._tokens[slot_idx] = 0
        self._positions[slot_idx] = 0
        return completion

    def paging_stats(self) -> Dict[str, Any]:
        """Point-in-time pool/prefix counters for /healthz and load tools."""
        stats: Dict[str, Any] = {
            "kv_pages_used": self.allocator.used_pages,
            "kv_pages_free": self.allocator.free_pages,
            "kv_pages_peak": self.allocator.peak_used,
            "kv_dtype": self.engine.kv_dtype,
            "kv_cache_bytes": self._kv_cache_bytes,
            "kv_bytes_per_token": round(self._kv_bytes_per_token, 4),
            "kv_used_bytes": self.allocator.used_bytes,
            "prefill_pad_share": round(
                self._pad_tokens / max(self._pad_tokens + self._prefill_tokens, 1), 4
            ),
        }
        if self.prefix_cache is not None:
            stats["prefix_cache"] = self.prefix_cache.stats()
        if self._spec != "off":
            stats["spec"] = self.spec_stats()
        stats["dispatch"] = self.dispatch_stats()
        stats["disagg"] = self.disagg_stats()
        return stats

    def disagg_stats(self) -> Dict[str, Any]:
        """Cumulative disaggregation counters — the /healthz ``disagg``
        block (role + migration/prefix-fetch economics) the smoke drill
        reads."""
        return {
            "role": self.role,
            "pages_migrated": self._pages_migrated,
            "migration_bytes": self._migration_bytes,
            "migration_failures": self._migration_failures,
            "migrated_inserts": self._migrated_inserts,
            "prefix_fetches": self._prefix_fetches,
            "prefix_fetch_failures": self._prefix_fetch_failures,
        }

    def dispatch_stats(self) -> Dict[str, Any]:
        """Cumulative dispatch-economics counters — the /healthz
        ``dispatch`` block; ``tests/test_packed.py`` reads it directly."""
        stats: Dict[str, Any] = {
            "mode": "packed" if self._packed else "sequential",
            "rounds": self._round_total,
            "model_dispatches": self._dispatch_total,
            "chunks_ahead": self._chunks_ahead,
            "dispatches_per_round": round(
                self._dispatch_total / max(self._round_total, 1), 4
            ),
            "tokens_total": self._dispatch_tokens,
            "tokens_real": self._dispatch_tokens_real,
            "tokens_per_dispatch": round(
                self._dispatch_tokens / max(self._dispatch_total, 1), 4
            ),
            "packed_token_utilization": round(
                self._dispatch_tokens_real / max(self._dispatch_tokens, 1), 4
            ),
            "admit_time_s": round(self._admit_time_s, 6),
            "decode_time_s": round(self._decode_time_s, 6),
            "prefill_stall_share": round(
                self._admit_time_s
                / max(self._admit_time_s + self._decode_time_s, 1e-9),
                4,
            ),
        }
        if self._packed:
            stats["token_budget"] = self.engine.token_budget
            stats["buckets"] = list(self.engine.packed_buckets())
        return stats

    def spec_stats(self) -> Dict[str, Any]:
        """Cumulative speculative-decoding counters — the /healthz ``spec``
        block; the speculation tests read accept rates from it."""
        return {
            "mode": self._spec,
            "k": self.engine.spec_k,
            "drafted": self._spec_drafted,
            "accepted": self._spec_accepted,
            "accept_rate": round(
                self._spec_accepted / max(self._spec_drafted, 1), 4
            ),
        }
