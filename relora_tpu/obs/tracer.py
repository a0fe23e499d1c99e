"""Span tracer: attributable wall-clock timing for training and serving.

The repo's two timing views before this module were aggregate (TTFT/TPOT
histograms on ``/metrics``, throughput lines in metrics.jsonl) or
device-level (``StepProfiler``'s XLA traces).  Neither can answer "where did
*this* request's 2 s TTFT go?" or "what fraction of a train step is host
metric pulls?".  Spans fill that gap: named wall-clock intervals with a
``trace_id`` (one per HTTP request / training run), a ``parent_id`` (so
phases nest into a tree), and free-form attributes.

Design constraints, in priority order:

1. **Hot-loop safe.**  ``Tracer.span`` is called some fifteen times per serving
   round and four times per train update; its cost is two ``time.monotonic()``
   calls, a few dict stores, one lock-guarded deque append and, once jax is
   loaded, two ``is_enabled()`` checks (a profiler annotation only while a
   session is live) — 7 µs on the chip's host, 27 inside a session, against
   rounds of tens and updates of hundreds of milliseconds (PERF.md §6,
   "PR 38").  No I/O on the hot path unless a JSONL sink is explicitly configured.
2. **Stdlib-only and jax-free**, like serve/admission and analysis/: the
   tracer must import fast and run in the asyncio front-end, the model
   thread, and the signal handler that dumps the flight recorder.  It never
   imports jax; where the process has already loaded it, ``Tracer.span``
   takes ``jax.profiler.TraceAnnotation`` from ``sys.modules`` (below).
3. **Thread-safe with cross-thread spans.**  Nesting uses a *per-thread*
   stack (the trainer's single-threaded loop gets parent/child links for
   free); spans that start on one thread and end on another (a request's
   queue-wait starts in an asyncio handler and ends in the model thread) use
   the explicit ``start_span()``/``Span.end()`` API.

Finished spans land in a :class:`~relora_tpu.obs.flight.FlightRecorder`
ring buffer (crash forensics) and, when configured, a JSONL stream.  Both
export to Chrome/Perfetto trace-event JSON (``chrome_trace_events``).

**The profiler's clock.**  A span's own stamps are ``time.monotonic()``; an
``.xplane.pb`` counts from its session's start, so the two do not line up.
A context-managed span therefore also enters a
``jax.profiler.TraceAnnotation`` of its name, with its scalar attributes and
its ``span_id``: while a ``jax.profiler`` session is live that puts the span
on the ``/host:CPU`` plane of the session's file, on the device planes'
clock by construction (``tools/trace_report.py --xplane`` reads both from
the one file).  Manual cross-thread spans get no annotation: a TraceMe scope
begins and ends on one thread.  A span inside which the session was live at
both ends is marked ``profiled: True`` and kept by the flight recorder's
capture (:meth:`FlightRecorder.capture`) until the next session.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, Iterable, List, Optional

__all__ = [
    "Span",
    "Tracer",
    "NoopTracer",
    "new_trace_id",
    "chrome_trace_events",
]


def new_trace_id() -> str:
    """A fresh 16-hex-char trace id (also used as HTTP X-Request-Id)."""
    return uuid.uuid4().hex[:16]


class Span:
    """One named wall-clock interval.  Mutable until :meth:`end` is called,
    which records it with the owning tracer exactly once."""

    __slots__ = (
        "name", "trace_id", "span_id", "parent_id", "t_start", "t_end",
        "attrs", "thread", "profiled", "_tracer", "_annotation",
    )

    def __init__(
        self,
        name: str,
        trace_id: str,
        span_id: str,
        parent_id: Optional[str],
        t_start: float,
        attrs: Dict[str, Any],
        tracer: "Tracer",
    ):
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t_start = t_start
        self.t_end: Optional[float] = None
        self.attrs = attrs
        self.thread = threading.current_thread().name
        # True: a profiler session was live at both ends; False: none was
        # live at the end; None: not observed (manual span, no jax, or the
        # session started inside the span)
        self.profiled: Optional[bool] = None
        self._tracer = tracer
        self._annotation = None  # the live TraceAnnotation of a span() block

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**_scalars(attrs))
        return self

    def drop(self) -> None:
        """Close the span without recording it (a scheduler round that
        turned out to have nothing to dispatch)."""
        if self.t_end is None:
            self.t_end = self._tracer.clock()

    @property
    def duration_s(self) -> Optional[float]:
        if self.t_end is None:
            return None
        return self.t_end - self.t_start

    def end(self) -> float:
        """Close the span and record it.  Returns the duration in seconds.
        Idempotent: a second call returns the recorded duration."""
        if self.t_end is None:
            self.t_end = self._tracer.clock()
            self._tracer._record(self)
        return self.t_end - self.t_start

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "t_start": self.t_start,
            "t_end": self.t_end,
            # wall-clock start: lets trace_report join spans from different
            # processes (each with its own monotonic origin) on one timeline
            "t_wall": self._tracer.wall_anchor + self.t_start,
            "dur_s": None if self.t_end is None else self.t_end - self.t_start,
            "thread": self.thread,
            "service": self._tracer.service,
            "attrs": self.attrs,
        }
        if self.profiled is not None:
            d["profiled"] = self.profiled
        return d


def _scalars(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """The attributes a profiler annotation can carry as event stats."""
    return {k: v for k, v in attrs.items() if isinstance(v, (bool, int, float, str))}


class Tracer:
    """Factory and sink for spans of one service ("train", "serve", ...).

    ``span()`` is the context-manager API with automatic per-thread nesting;
    ``start_span()``/``Span.end()`` is the manual API for spans that cross
    threads (they do not touch the nesting stack).  ``event()`` records an
    instant (zero-duration) marker.
    """

    def __init__(
        self,
        service: str = "app",
        *,
        recorder=None,
        jsonl_path: Optional[str] = None,
        clock=time.monotonic,
    ):
        self.service = service
        self.clock = clock
        self.enabled = True
        # epoch anchor: wall time at construction minus the monotonic origin,
        # so exports can map monotonic stamps to wall clock
        self.wall_anchor = time.time() - clock()
        self.default_trace_id = new_trace_id()
        if recorder is None:
            from relora_tpu.obs.flight import default_recorder

            recorder = default_recorder()
        self.recorder = recorder
        self._ids = itertools.count(1)  # next() is atomic in CPython
        self._local = threading.local()
        # jax.profiler.TraceAnnotation, taken from sys.modules the first time
        # a span opens after the process has loaded jax; never imported here
        self._annotation_cls = None
        self._jsonl_lock = threading.Lock()
        self._jsonl_path = jsonl_path
        self._jsonl_fh = None
        if jsonl_path:
            os.makedirs(os.path.dirname(os.path.abspath(jsonl_path)), exist_ok=True)
            self._jsonl_fh = open(jsonl_path, "a")

    # -- internals -----------------------------------------------------------

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _next_id(self) -> str:
        return f"s{next(self._ids):06x}"

    def _trace_annotation(self):
        cls = self._annotation_cls
        if cls is None:
            # a half-imported jax has no ``profiler`` yet: look again next time
            profiler = getattr(sys.modules.get("jax"), "profiler", None)
            cls = self._annotation_cls = getattr(profiler, "TraceAnnotation", None)
        return cls

    def _record(self, span: Span) -> None:
        d = span.to_dict()
        self.recorder.add_span(d)
        fh = self._jsonl_fh
        if fh is not None:
            with self._jsonl_lock:
                fh.write(json.dumps(d) + "\n")
                fh.flush()

    # -- public API ----------------------------------------------------------

    def start_span(
        self,
        name: str,
        *,
        trace_id: Optional[str] = None,
        parent: Optional[Span] = None,
        **attrs: Any,
    ) -> Span:
        """Manual span (cross-thread capable): caller must call ``end()``.
        Does not join the per-thread nesting stack, but *reads* it: with no
        explicit parent, the calling thread's current span becomes the
        parent — unless an explicit ``trace_id`` names another trace (a
        request's ``decode`` span opened inside a batch-level ``round`` is
        the request's, not the round's)."""
        stack = self._stack()
        top = stack[-1] if stack else None
        if parent is None and (trace_id is None or top is None or top.trace_id == trace_id):
            parent = top
        if trace_id is None:
            trace_id = parent.trace_id if parent is not None else self.default_trace_id
        return Span(
            name,
            trace_id,
            self._next_id(),
            parent.span_id if parent is not None else None,
            self.clock(),
            attrs,
            self,
        )

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        *,
        trace_id: Optional[str] = None,
        parent: Optional[Span] = None,
        **attrs: Any,
    ):
        """Context-managed span with automatic nesting: children opened in
        the same thread inside this block parent to it.  Where jax is loaded
        the block is also a ``jax.profiler.TraceAnnotation`` (module
        docstring, "The profiler's clock")."""
        sp = self.start_span(name, trace_id=trace_id, parent=parent, **attrs)
        stack = self._stack()
        stack.append(sp)
        annotation_cls = self._trace_annotation()
        # with no session live the annotation would record nothing (nor would
        # a session that starts inside the span take it up): two is_enabled()
        # checks are all a span costs the profiler's way then
        live = annotation_cls is not None and annotation_cls.is_enabled()
        if live:
            sp._annotation = annotation_cls(name, span_id=sp.span_id, **_scalars(attrs))
            sp._annotation.__enter__()
        try:
            yield sp
        finally:
            # pop by identity: an exception inside a nested manual pop can't
            # desync the stack
            if stack and stack[-1] is sp:
                stack.pop()
            elif sp in stack:
                stack.remove(sp)
            if live:
                annotation, sp._annotation = sp._annotation, None
                annotation.__exit__(None, None, None)
            if annotation_cls is not None:
                # both ends: the span inside which a session starts or stops
                # is left out of the capture
                if annotation_cls.is_enabled():
                    sp.profiled = True if live else None
                else:
                    sp.profiled = False
            sp.end()

    def current_span(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    def event(self, name: str, *, trace_id: Optional[str] = None, **attrs: Any) -> None:
        """Instant marker (Chrome phase "i"): zero-duration, recorded
        immediately."""
        top = self.current_span()
        if trace_id is None:
            trace_id = top.trace_id if top is not None else self.default_trace_id
        t = self.clock()
        record = {
            "name": name,
            "trace_id": trace_id,
            "parent_id": top.span_id if top is not None else None,
            "t": t,
            "t_wall": self.wall_anchor + t,
            "thread": threading.current_thread().name,
            "service": self.service,
            "attrs": attrs,
        }
        self.recorder.add_event(record)
        fh = self._jsonl_fh
        if fh is not None:
            with self._jsonl_lock:
                fh.write(json.dumps({"_event": True, **record}) + "\n")
                fh.flush()

    def close(self) -> None:
        fh, self._jsonl_fh = self._jsonl_fh, None
        if fh is not None:
            with self._jsonl_lock:
                fh.close()


class _NoopSpan:
    __slots__ = ()
    name = trace_id = span_id = parent_id = thread = ""
    parent_id = None
    t_start = t_end = 0.0
    duration_s = 0.0
    attrs: Dict[str, Any] = {}

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def drop(self) -> None:
        return None

    def end(self) -> float:
        return 0.0

    def to_dict(self) -> Dict[str, Any]:
        return {}


_NOOP_SPAN = _NoopSpan()


class _NoopCtx:
    __slots__ = ()

    def __enter__(self) -> _NoopSpan:
        return _NOOP_SPAN

    def __exit__(self, *exc) -> None:
        return None


_NOOP_CTX = _NoopCtx()


class NoopTracer:
    """API-compatible tracer that records nothing — the control arm of the
    overhead bench and what a scheduler built without a tracer holds."""

    enabled = False
    service = "noop"
    clock = staticmethod(time.monotonic)
    wall_anchor = 0.0
    default_trace_id = "0" * 16

    def span(self, name: str, **kw: Any) -> _NoopCtx:
        return _NOOP_CTX

    def start_span(self, name: str, **kw: Any) -> _NoopSpan:
        return _NOOP_SPAN

    def current_span(self) -> None:
        return None

    def event(self, name: str, **kw: Any) -> None:
        return None

    def close(self) -> None:
        return None


def chrome_trace_events(
    spans: Iterable[Dict[str, Any]],
    events: Iterable[Dict[str, Any]] = (),
    *,
    pid: Optional[int] = None,
) -> List[Dict[str, Any]]:
    """Convert recorded span/event dicts to Chrome trace-event JSON objects
    (the ``traceEvents`` list).  Timestamps are ``time.monotonic()``
    microseconds (``tools/trace_report.py`` shifts them onto wall time to
    join a router's spans with its replicas').  That is not the XLA
    profiler's clock: an ``.xplane.pb`` counts from its session's start.  To
    see host spans against device activity, read the annotations
    ``Tracer.span`` leaves in the profile itself
    (``tools/trace_report.py --xplane``)."""
    pid = os.getpid() if pid is None else pid
    out: List[Dict[str, Any]] = []
    tids: Dict[str, int] = {}

    def tid_of(thread: str) -> int:
        if thread not in tids:
            tids[thread] = len(tids) + 1
        return tids[thread]

    for s in spans:
        if s.get("t_end") is None:
            continue
        out.append(
            {
                "name": s["name"],
                "cat": s.get("service", "obs"),
                "ph": "X",
                "ts": round(s["t_start"] * 1e6, 3),
                "dur": round((s["t_end"] - s["t_start"]) * 1e6, 3),
                "pid": pid,
                "tid": tid_of(s.get("thread", "main")),
                "args": {
                    "trace_id": s.get("trace_id"),
                    "span_id": s.get("span_id"),
                    "parent_id": s.get("parent_id"),
                    **(s.get("attrs") or {}),
                },
            }
        )
    for e in events:
        out.append(
            {
                "name": e["name"],
                "cat": e.get("service", "obs"),
                "ph": "i",
                "s": "t",
                "ts": round(e["t"] * 1e6, 3),
                "pid": pid,
                "tid": tid_of(e.get("thread", "main")),
                "args": {"trace_id": e.get("trace_id"), **(e.get("attrs") or {})},
            }
        )
    for thread, tid in tids.items():
        out.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": thread},
            }
        )
    return out
