"""Readers over the program's own spans: those of the traced part of the
window, which the program's flight recorder keeps after the run.

``relora_tpu.obs.tracer.Tracer.span`` marks a span ``profiled`` when a
``jax.profiler`` session was live at both its ends, and the process-wide
``relora_tpu.obs.flight.default_recorder()`` keeps those until the next
session (``capture()``).  The harness's ``--trace 1`` run is such a session,
so these readers see the serving rounds or the training updates that lay
wholly inside it: what the scheduler or the trainer timed itself, at the
place the work happens.  A program without the capture (an older commit)
gives every reader nothing to read, and the metric is left out.
"""

from __future__ import annotations

import re
from typing import Optional


def captured() -> list:
    """The span dicts of the last profiler session, or ``[]``."""
    from relora_tpu.obs import flight

    capture = getattr(flight.default_recorder(), "capture", None)
    return capture() if capture is not None else []


def rounds(spans: list) -> list:
    """``[(round span, its descendants)]`` for every ``round`` that
    dispatched work, children found through ``parent_id``."""
    by_parent: dict = {}
    for s in spans:
        by_parent.setdefault(s.get("parent_id"), []).append(s)

    def below(span: dict) -> list:
        out = []
        for child in by_parent.get(span["span_id"], []):
            out += [child, *below(child)]
        return out

    return [(s, below(s)) for s in spans if s["name"] == "round" and s.get("attrs", {}).get("dispatches")]


def _mean(values: list) -> Optional[float]:
    return sum(values) / len(values) if values else None


def round_host_ms(obs: dict) -> Optional[float]:
    """Mean host-only milliseconds of a scheduler round: its duration less the
    interval from its first ``dispatch`` (or ``prefill_chunk``) start to its
    last ``pull`` end, in which the device has work or the host waits for it."""
    host = []
    for r, inside in rounds(captured()):
        starts = [s["t_start"] for s in inside if s["name"] in ("dispatch", "prefill_chunk")]
        ends = [s["t_end"] for s in inside if s["name"] == "pull"]
        if starts and ends:
            host.append(1e3 * ((r["t_end"] - r["t_start"]) - (max(ends) - min(starts))))
    return _mean(host)


def pull_wait_ms(obs: dict) -> Optional[float]:
    """Mean milliseconds a round spends inside ``pull`` spans: how long the
    host waits for the device."""
    return _mean([1e3 * sum(s["dur_s"] for s in inside if s["name"] == "pull") for _, inside in rounds(captured())])


def mean_ms(obs: dict, name: str) -> Optional[float]:
    """Mean duration of the captured spans called ``name``."""
    return _mean([1e3 * s["dur_s"] for s in captured() if s["name"] == name])


def bytes_roofline_pct(obs: dict, span: str, attr: str, pattern: str, program: str) -> Optional[float]:
    """A memory-bound kernel's share of its roofline, per dispatch: the least
    time to read the bytes the program counted (mean ``attr`` of the captured
    ``span`` spans over the peak bytes/s) over the kernel's device time per
    dispatch (summed time of the trace's operations matching ``pattern`` over
    the executions of the XLA ``program`` that runs them).  A ratio of
    per-dispatch means, so a dispatch cut by the trace's edge does not bias it."""
    trace = obs.get("trace") or {}
    need = _mean([s["attrs"][attr] for s in captured() if s["name"] == span and s.get("attrs", {}).get(attr)])
    seconds = sum(v for k, v in trace.get("ops", {}).items() if re.search(pattern, k))
    runs = trace.get("programs", {}).get(program, {}).get("executions")
    if not need or not seconds or not runs or not obs.get("peak"):
        return None
    return 100.0 * (need / obs["peak"]["hbm_bytes_per_s"]) / (seconds / runs)
