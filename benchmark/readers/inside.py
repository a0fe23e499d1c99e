"""Device operations inside the executions of one XLA program.

``readers/trace.py`` sums an operation's name over every program of the
trace.  Two programs that run the same kind of operation — the decode and the
prefill chunk of a model with routed experts both run ``%ragged-dot-none*`` —
cannot be told apart by name there.  :func:`ops_inside` keeps, per name, only
the events that started inside an execution of the named program (the device
plane's ``XLA Modules`` line gives the executions' intervals); a driver calls
it on the trace before ``run.py`` reduces and removes it, and puts the result
under ``obs["inside"][program]``.  The readers below take their pattern from
the metric's file.  A run with no such entry (an untraced run, a driver that
makes none) gives them nothing to read.
"""

from __future__ import annotations

import bisect
import re
from typing import Optional

from benchmark.readers import trace


def ops_inside(planes: dict, program: str) -> dict:
    """``{"ops": {name: seconds}, "executions": n}`` averaged over the device
    planes: the operations that started inside an execution of ``program``."""
    ops: dict = {}
    executions = 0
    for lines in planes.values():
        spans = sorted(
            (start, start + dur)
            for name, start, dur in lines.get(trace.MODULES_LINE, [])
            if trace._program_name(name) == program
        )
        executions += len(spans)
        starts = [s for s, _ in spans]
        for name, start, dur in lines.get(trace.OPS_LINE, []):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < spans[i][1]:
                ops[name] = ops.get(name, 0.0) + dur / 1e9
    n = max(len(planes), 1)
    return {"ops": {k: v / n for k, v in ops.items()}, "executions": executions / n}


def _seconds_per_execution(obs: dict, program: str, pattern: str) -> Optional[float]:
    inside = (obs.get("inside") or {}).get(program)
    if not inside or not inside["executions"]:
        return None
    hit = [v for k, v in inside["ops"].items() if re.search(pattern, k)]
    return sum(hit) / inside["executions"] if hit else None


def pattern_ms(obs: dict, program: str, pattern: str) -> Optional[float]:
    """Device milliseconds of the operations matching ``pattern`` per
    execution of ``program``."""
    s = _seconds_per_execution(obs, program, pattern)
    return None if s is None else 1e3 * s


def bytes_roofline_pct(obs: dict, span: str, attr: str, pattern: str, program: str) -> Optional[float]:
    """``spans.bytes_roofline_pct`` with the kernel's time taken inside
    ``program``'s executions only: the least time to read the bytes the
    program counted (mean ``attr`` of the captured ``span`` spans over the peak
    bytes/s) over the matching operations' device time per execution."""
    from benchmark.readers import spans

    values = [s["attrs"][attr] for s in spans.captured() if s["name"] == span and s.get("attrs", {}).get(attr)]
    seconds = _seconds_per_execution(obs, program, pattern)
    if not values or not seconds or not obs.get("peak"):
        return None
    need = sum(values) / len(values)
    return 100.0 * (need / obs["peak"]["hbm_bytes_per_s"]) / seconds
