"""From a ``jax.profiler`` trace to numbers.  Four outputs: each device's
busy and idle share, device time per XLA program by name, the summed duration
of the device operations whose name matches a pattern, and the breakdown (top
device operations, longest idle gaps).

The reduction works on plain events ``(name, start_ns, duration_ns)`` grouped
by plane and line, so that it can be checked on a small recorded trace
(``benchmark/tests/data/trace_events.json``); :func:`load_xplane` makes those
events from the ``.xplane.pb`` the profiler wrote.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, duration_ns), ...]}}`` for the device
    planes' operation and program lines."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [(short_name(e.name), float(e.start_ns), float(e.duration_ns)) for e in line.events]
    return out


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO text; keep the instruction's
    name (``%fusion.12``), and for a custom call its target beside it."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{head} [{target.group(1)}]" if target else head


def _self_ns(events: list) -> dict:
    """Per name, the time its events ran less the time of the events nested
    inside them (a ``while`` holds its body's operations)."""
    out: dict = {}
    stack: list = []  # (stop, name)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= min(dur, stack[-1][0] - start)
        out[name] = out.get(name, 0.0) + dur
        stack.append((start + dur, name))
    return out


def _union_ns(events: list) -> float:
    busy, end = 0.0, float("-inf")
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if start > end:
            busy += dur
            end = stop
        elif stop > end:
            busy += stop - end
            end = stop
    return busy


def _gaps(events: list) -> list:
    """Idle gaps ``(start_ns, duration_ns, op_before, op_after)`` between
    device operations, longest first."""
    gaps, end, last = [], None, None
    for name, start, dur in sorted(events, key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append((end, start - end, last, name))
        if end is None or start + dur > end:
            end, last = start + dur, name
    return sorted(gaps, key=lambda g: -g[1])


def _program_name(event_name: str) -> str:
    """``jit_train_step(123456)`` -> ``jit_train_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(planes: dict, window_s: float) -> dict:
    """The four outputs, averaged over the device planes.  ``window_s`` is the
    traced window on the host clock; the device's busy time cannot exceed it
    by more than clock skew, and the idle share is taken against it."""
    if not planes:
        return {}
    busy, programs, ops, self_ops, gaps = [], {}, {}, {}, []
    for lines in planes.values():
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy.append(_union_ns(op_events) / 1e9)
        for name, _, dur in lines.get(MODULES_LINE, []):
            p = programs.setdefault(_program_name(name), [0.0, 0])
            p[0] += dur / 1e9
            p[1] += 1
        for name, _, dur in op_events:
            ops[name] = ops.get(name, 0.0) + dur / 1e9
        for name, ns in _self_ns(op_events).items():
            self_ops[name] = self_ops.get(name, 0.0) + ns / 1e9
        gaps += _gaps(op_events)
    n = len(planes)
    busy_s = sum(busy) / n
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": max(0.0, 1.0 - busy_s / window_s) if window_s > 0 else None,
        "programs": {k: {"device_s": v[0] / n, "executions": v[1] / n} for k, v in programs.items()},
        "ops": {k: v / n for k, v in ops.items()},
        "self_ops": {k: v / n for k, v in self_ops.items()},
        "gaps": sorted(gaps, key=lambda g: -g[1])[:10],
    }


def breakdown(summary: dict) -> dict:
    """The ten device operations that took most time of their own (what is
    nested inside a loop counts for the nested operation), and the ten longest
    idle gaps, each named by the operations on either side of it."""
    top = sorted(summary.get("self_ops", {}).items(), key=lambda kv: -kv[1])[:10]
    gaps = [[f"{before} -> {after}", dur / 1e9] for _, dur, before, after in summary.get("gaps", [])]
    return {"device_ops": [[k, v] for k, v in top], "idle_gaps": gaps}


# -- readers: (obs, **args) -> value or None --------------------------------


def program_ms(obs: dict, program: str) -> Optional[float]:
    """Device milliseconds of one execution of an XLA program, by name."""
    p = (obs.get("trace") or {}).get("programs", {}).get(program)
    if not p or not p["executions"]:
        return None
    return 1e3 * p["device_s"] / p["executions"]


def idle_share_pct(obs: dict) -> Optional[float]:
    share = (obs.get("trace") or {}).get("idle_share")
    return None if share is None else 100.0 * share


def pattern_seconds(obs: dict, pattern: str) -> Optional[float]:
    """Summed device seconds of the operations whose name matches ``pattern``."""
    ops = (obs.get("trace") or {}).get("ops", {})
    hit = [v for k, v in ops.items() if re.search(pattern, k)]
    return sum(hit) if hit else None


def kernel_roofline_pct(obs: dict, pattern: str, work: str) -> Optional[float]:
    """A kernel's share of its roofline: the least time the chip could take
    for the FLOPs and bytes the driver counted under ``work`` (the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s) over the summed
    device time of the operations matching ``pattern``."""
    seconds = pattern_seconds(obs, pattern)
    need = obs.get("work", {}).get(work)
    if not seconds or not need or not need.get("flops"):
        return None
    peak = obs["peak"]
    least = max(need["flops"] / peak["flops_per_s"], need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
