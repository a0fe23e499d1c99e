"""Flight recorder: a bounded ring buffer of recent spans and events.

The last N spans before a fault are the forensics that aggregate metrics
cannot provide: *what was the run doing* when SIGTERM landed, when the loss
spiked, or when the process crashed?  Tracers feed every finished span into
a process-wide :class:`FlightRecorder` (deque ring buffers — O(1) append,
bounded memory, no I/O); ``train/resilience.PreemptionGuard`` and the
trainer's crash/rollback paths call :func:`dump_on_fault` to write the
buffer to disk as JSON that ``tools/trace_report.py`` renders.

Beside the ring the recorder keeps **the capture**: the spans of the last
``jax.profiler`` session, which the tracer marks ``profiled`` (a session was
live at both their ends).  The ring turns over in seconds under serving
traffic (some fourteen spans a round); the capture is only cleared by the next
session's first span, so it is the host half of a captured profile — the
trainer's ``--profile`` as much as a benchmark's traced run — and what
:meth:`FlightRecorder.capture` hands a reader after the run.

Dump location, first match wins: ``RELORA_TPU_FLIGHT_DIR`` env, the dir set
via :func:`configure` (the trainer points this at ``save_dir``), the
current directory.  Dumps are written atomically (tmp + rename) because the
SIGTERM path may be mid-write when the process is killed for real.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "FlightRecorder",
    "default_recorder",
    "configure",
    "dump_on_fault",
]

#: ring capacities — ~2k spans are the last 140 serving rounds (a round
#: leaves about fourteen spans, a request four of its own, its one
#: ``sse_flush`` among them: five seconds at 28 rounds a second) or hundreds
#: of train steps, at <1 MB resident; sized for forensics, not archival
SPAN_CAPACITY = 2048
EVENT_CAPACITY = 512
#: the capture's bound: a profiler session is seconds long.  A serving round
#: leaves about fourteen context-managed spans (a train update four), so a
#: 4 s session of 15 ms rounds is 3,700; a reader takes no mean over a
#: capture that dropped any (``dropped_profiled``)
CAPTURE_CAPACITY = 16384


class FlightRecorder:
    """Thread-safe ring buffer of span/event dicts with atomic JSON dumps."""

    def __init__(
        self,
        span_capacity: int = SPAN_CAPACITY,
        event_capacity: int = EVENT_CAPACITY,
        capture_capacity: int = CAPTURE_CAPACITY,
    ):
        self._lock = threading.Lock()
        self._spans: "collections.deque[Dict[str, Any]]" = collections.deque(maxlen=span_capacity)
        self._events: "collections.deque[Dict[str, Any]]" = collections.deque(maxlen=event_capacity)
        self.dropped_spans = 0  # total appends beyond capacity
        self._capture: List[Dict[str, Any]] = []
        self._capture_capacity = capture_capacity
        self._capture_open = False  # a profiled span came since the last unprofiled end
        self.dropped_profiled = 0  # profiled spans of this session beyond the bound

    def add_span(self, span: Dict[str, Any]) -> None:
        profiled = span.get("profiled")
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped_spans += 1
            self._spans.append(span)
            if profiled:
                if not self._capture_open:
                    # the first span of a new session: the last one's go
                    self._capture_open = True
                    self._capture.clear()
                    self.dropped_profiled = 0
                if len(self._capture) < self._capture_capacity:
                    self._capture.append(span)
                else:
                    self.dropped_profiled += 1
            elif profiled is False:
                # a span ended with no session live: whatever is profiled
                # next belongs to another session
                self._capture_open = False

    def add_event(self, event: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(event)

    def spans(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._spans)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def capture(self) -> List[Dict[str, Any]]:
        """The ``profiled`` spans of the last profiler session, in the order
        they ended (at most the capture's bound: ``dropped_profiled``)."""
        with self._lock:
            return list(self._capture)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._events.clear()
            self.dropped_spans = 0
            self._capture.clear()
            self._capture_open = False
            self.dropped_profiled = 0

    def dump(self, path: str, reason: str = "manual") -> str:
        """Write the buffer as JSON (atomic rename).  Returns the path."""
        with self._lock:
            payload = {
                "reason": reason,
                "wall_time": time.time(),
                "pid": os.getpid(),
                "dropped_spans": self.dropped_spans,
                "spans": list(self._spans),
                "events": list(self._events),
                "dropped_profiled": self.dropped_profiled,
                "profiled_spans": list(self._capture),
            }
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(payload, fh)
        os.replace(tmp, path)
        return path


# -- process default ---------------------------------------------------------

_RECORDER: Optional[FlightRecorder] = None
_RECORDER_LOCK = threading.Lock()
_DUMP_DIR: Optional[str] = None


def default_recorder() -> FlightRecorder:
    global _RECORDER
    with _RECORDER_LOCK:
        if _RECORDER is None:
            _RECORDER = FlightRecorder()
        return _RECORDER


def configure(dump_dir: Optional[str] = None) -> None:
    """Set the preferred dump directory (the trainer passes its save_dir)."""
    global _DUMP_DIR
    _DUMP_DIR = dump_dir


def _dump_dir() -> str:
    return os.environ.get("RELORA_TPU_FLIGHT_DIR") or _DUMP_DIR or "."


def dump_on_fault(reason: str) -> Optional[str]:
    """Dump the default recorder to ``<dir>/flight_<reason>_<pid>.json``.

    Fault-path safe: never raises (a failed dump must not mask the original
    fault or break the signal handler), returns None if the buffer is empty
    or the write fails.
    """
    rec = default_recorder()
    try:
        if not rec.spans() and not rec.events():
            return None
        path = os.path.join(_dump_dir(), f"flight_{reason}_{os.getpid()}.json")
        return rec.dump(path, reason=reason)
    except Exception:
        return None
