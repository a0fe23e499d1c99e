#!/usr/bin/env python
"""Render span traces: tree view, per-phase percentages, p50/p95 tables.

Reads either a flight-recorder dump (``flight_<reason>_<pid>.json``, written
by ``relora_tpu.obs.flight.dump_on_fault``) or a JSONL span stream (one span
dict per line — the trainer's ``RELORA_TPU_TRACE_DIR`` sink).  Prints:

1. a span tree per trace (``--trace`` selects one; default: the few most
   recent), children indented under parents, with duration and the share of
   the root span's wall time;
2. a phase summary across ALL loaded spans: count, total seconds, p50/p95,
   and percentage of the total traced time per span name.

``--chrome OUT.json`` additionally exports everything as Chrome trace-event
JSON — open in chrome://tracing or https://ui.perfetto.dev, where it overlays
with the XLA timelines StepProfiler writes.

``--xplane FILE.xplane.pb`` reads a ``jax.profiler`` profile instead: the
device planes' operations and, from the ``/host:CPU`` plane of the same file
and so on the same clock, the annotations ``Tracer.span`` left there.  It
prints each device idle gap over a millisecond with the innermost host span
open at its midpoint, the gap seconds per span name and per scheduler round
(and, since a long gap covers several spans, all idle time inside the whole
rounds by the innermost span it overlaps: the table that agrees with the
scheduler's own ``round.host_gap_ms``), each round's host-only time (its duration less the interval from its
first ``dispatch`` or ``prefill_chunk`` start to its last ``pull`` end), and
the XLA programs the device executed, by name and per round.

    python tools/trace_report.py ckpts/flight_sigterm_1234.json
    python tools/trace_report.py traces/train_spans.jsonl --trace a1b2c3
    python tools/trace_report.py dump.json --chrome /tmp/trace.json
    python tools/trace_report.py --xplane .bench_work/<cell>/trace/plugins/profile/<t>/<host>.xplane.pb
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

# runnable from any cwd without an installed package
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from relora_tpu.obs.tracer import chrome_trace_events  # noqa: E402


def load(path: str) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]], Dict[str, Any]]:
    """Return (spans, events, header) from a flight dump or a JSONL stream."""
    if path.endswith(".jsonl"):
        spans: List[Dict[str, Any]] = []
        events: List[Dict[str, Any]] = []
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    record = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn tail line from a killed writer
                # instant events share the stream, flagged with _event
                if record.pop("_event", None):
                    events.append(record)
                else:
                    spans.append(record)
        return spans, events, {"source": "jsonl"}
    with open(path) as fh:
        payload = json.load(fh)
    header = {k: v for k, v in payload.items() if k not in ("spans", "events")}
    return payload.get("spans", []), payload.get("events", []), header


def merge_streams(
    streams: List[Tuple[str, List[Dict[str, Any]], List[Dict[str, Any]]]],
) -> Tuple[List[Dict[str, Any]], List[Dict[str, Any]]]:
    """Join span streams from different processes into one timeline.

    Each process numbers its spans independently ("s000001" collides across
    files), so span/parent ids get a per-stream prefix — parent links stay
    intra-process, while the shared ``trace_id`` (the router's X-Request-Id)
    joins the trees.  Timestamps are per-process *monotonic* clocks with
    unrelated origins; spans recorded since ``t_wall`` exists are shifted
    onto the wall clock so router and replica phases interleave correctly.
    Every span/event is tagged with ``_pid`` (stream index + 1) and
    ``_stream`` (stream name) for the Chrome export's per-process grouping.
    """
    merged_spans: List[Dict[str, Any]] = []
    merged_events: List[Dict[str, Any]] = []
    for i, (name, spans, events) in enumerate(streams):
        prefix = f"p{i}:"
        for s in spans:
            s = dict(s)
            if s.get("span_id"):
                s["span_id"] = prefix + str(s["span_id"])
            if s.get("parent_id"):
                s["parent_id"] = prefix + str(s["parent_id"])
            t_wall = s.get("t_wall")
            if isinstance(t_wall, (int, float)) and s.get("t_start") is not None:
                shift = t_wall - s["t_start"]
                s["t_start"] = t_wall
                if s.get("t_end") is not None:
                    s["t_end"] = s["t_end"] + shift
            s["_pid"], s["_stream"] = i + 1, name
            merged_spans.append(s)
        for e in events:
            e = dict(e)
            if e.get("parent_id"):
                e["parent_id"] = prefix + str(e["parent_id"])
            if isinstance(e.get("t_wall"), (int, float)):
                e["t"] = e["t_wall"]
            e["_pid"], e["_stream"] = i + 1, name
            merged_events.append(e)
    # re-zero at the earliest stamp: wall-epoch microseconds confuse trace
    # viewers and make the tree's ms column unreadable
    t0 = min(
        [s["t_start"] for s in merged_spans if s.get("t_start") is not None]
        + [e["t"] for e in merged_events if e.get("t") is not None]
        or [0.0]
    )
    for s in merged_spans:
        if s.get("t_start") is not None:
            s["t_start"] -= t0
        if s.get("t_end") is not None:
            s["t_end"] -= t0
    for e in merged_events:
        if e.get("t") is not None:
            e["t"] -= t0
    merged_spans.sort(key=lambda s: s.get("t_start") or 0.0)
    merged_events.sort(key=lambda e: e.get("t") or 0.0)
    return merged_spans, merged_events


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over raw durations (exact, not bucketed)."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _fmt_attrs(attrs: Dict[str, Any], limit: int = 4) -> str:
    if not attrs:
        return ""
    items = list(attrs.items())[:limit]
    body = " ".join(f"{k}={v}" for k, v in items)
    more = "" if len(attrs) <= limit else " …"
    return f"  [{body}{more}]"


def print_tree(spans: List[Dict[str, Any]], trace_id: str, out=sys.stdout) -> None:
    trace = [s for s in spans if s.get("trace_id") == trace_id]
    by_id = {s["span_id"]: s for s in trace}
    children: Dict[Optional[str], List[Dict[str, Any]]] = {}
    for s in trace:
        parent = s.get("parent_id")
        # a parent evicted from the ring buffer orphans its children: show
        # them at the root rather than dropping them
        if parent not in by_id:
            parent = None
        children.setdefault(parent, []).append(s)
    for group in children.values():
        group.sort(key=lambda s: s.get("t_start") or 0.0)
    roots = children.get(None, [])
    total = sum(s.get("dur_s") or 0.0 for s in roots) or None
    # a cross-process trace (router + replica joined on one request id)
    # qualifies span names with their service so the tree reads as a hop
    # sequence; single-service traces render exactly as before
    services = {s.get("service") for s in trace if s.get("service")}
    qualify = len(services) > 1
    out.write(f"trace {trace_id}  ({len(trace)} spans)\n")

    def walk(span: Dict[str, Any], depth: int) -> None:
        dur = span.get("dur_s")
        dur_txt = "open" if dur is None else f"{dur * 1e3:.2f} ms"
        pct = ""
        if total and dur is not None:
            pct = f"  {100.0 * dur / total:5.1f}%"
        name = span.get("name", "?")
        if qualify:
            name = f"{span.get('service', '?')}/{name}"
        out.write(
            f"  {'  ' * depth}{name}  {dur_txt}{pct}"
            f"{_fmt_attrs(span.get('attrs') or {})}\n"
        )
        for child in children.get(span["span_id"], []):
            walk(child, depth + 1)

    for root in roots:
        walk(root, 0)


def phase_summary(spans: List[Dict[str, Any]], out=sys.stdout) -> None:
    by_name: Dict[str, List[float]] = {}
    for s in spans:
        dur = s.get("dur_s")
        if dur is not None:
            by_name.setdefault(s.get("name", "?"), []).append(dur)
    if not by_name:
        out.write("no finished spans\n")
        return
    # % is of the summed time across all phases — sibling phases of one step
    # roughly partition it, so the column reads as "where did the time go"
    grand_total = sum(sum(v) for v in by_name.values())
    out.write(
        f"\n{'phase':<20} {'count':>6} {'total_s':>9} {'p50_ms':>9} "
        f"{'p95_ms':>9} {'share':>7}\n"
    )
    for name, vals in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
        vals.sort()
        total = sum(vals)
        out.write(
            f"{name:<20} {len(vals):>6} {total:>9.3f} "
            f"{percentile(vals, 0.50) * 1e3:>9.2f} "
            f"{percentile(vals, 0.95) * 1e3:>9.2f} "
            f"{100.0 * total / grand_total:>6.1f}%\n"
        )


# -- a jax.profiler profile: device idle gaps put down to host spans ------------

#: the device planes' per-operation line, their per-program line (one event
#: for each execution of an XLA program), and the shortest gap worth a line
XLA_OPS_LINE = "XLA Ops"
XLA_MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 1e6
NO_SPAN = "(no span open)"


def _load_profile(path: str):
    """``(device operations, device programs, host spans)`` of one
    ``.xplane.pb``: see :func:`load_xplane` and :func:`load_programs`."""
    from jax.profiler import ProfileData

    device_ops: Dict[str, List[Tuple[float, float]]] = {}
    programs: Dict[str, List[Tuple[float, str]]] = {}
    host_spans: List[Dict[str, Any]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == XLA_OPS_LINE:
                    device_ops[plane.name] = [
                        (float(e.start_ns), float(e.duration_ns)) for e in line.events
                    ]
                elif line.name == XLA_MODULES_LINE:
                    programs[plane.name] = sorted(
                        (float(e.start_ns), e.name.split("(")[0]) for e in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("$"):
                        continue  # the Python tracer's own function events
                    stats = dict(e.stats)
                    if "span_id" in stats:
                        host_spans.append(
                            {
                                "name": e.name,
                                "start_ns": float(e.start_ns),
                                "dur_ns": float(e.duration_ns),
                                "thread": line.name,
                                "attrs": stats,
                            }
                        )
    host_spans.sort(key=lambda s: s["start_ns"])
    return device_ops, programs, host_spans


def load_xplane(path: str) -> Tuple[Dict[str, List[Tuple[float, float]]], List[Dict[str, Any]]]:
    """``({device plane: [(start_ns, duration_ns)]}, host spans)`` of one
    ``.xplane.pb``.  A host span is an event of the ``/host:CPU`` plane that
    carries a ``span_id`` stat, which only ``Tracer.span``'s annotations do:
    ``{"name", "start_ns", "dur_ns", "thread", "attrs"}``."""
    device_ops, _, host_spans = _load_profile(path)
    return device_ops, host_spans


def load_programs(path: str) -> Dict[str, List[Tuple[float, str]]]:
    """``{device plane: [(start_ns, program name)]}``: one entry for each
    execution of an XLA program (an event of the "XLA Modules" line), the
    name without the fingerprint the profiler appends in brackets."""
    return _load_profile(path)[1]


def programs_per_frame(
    programs: List[Tuple[float, str]], frames: List[Tuple[float, float]]
) -> Dict[str, float]:
    """Executions per frame, by program name, of the programs that started
    inside one of ``frames`` (``(start_ns, end_ns)``: the whole rounds)."""
    counts: Dict[str, float] = {}
    for start, name in programs:
        if any(lo <= start < hi for lo, hi in frames):
            counts[name] = counts.get(name, 0.0) + 1.0 / len(frames)
    return counts


def idle_gaps(ops: List[Tuple[float, float]], min_ns: float = MIN_GAP_NS) -> List[Tuple[float, float]]:
    """``(start_ns, duration_ns)`` of the gaps of at least ``min_ns`` between
    the device's operations (their union: a loop's body lies inside it)."""
    gaps, end = [], None
    for start, dur in sorted(ops):
        if end is not None and start - end >= min_ns:
            gaps.append((end, start - end))
        end = start + dur if end is None else max(end, start + dur)
    return gaps


def innermost_span(spans: List[Dict[str, Any]], t_ns: float) -> Optional[Dict[str, Any]]:
    """The span open at ``t_ns`` that started last (``spans`` sorted by start)."""
    best = None
    for s in spans:
        if s["start_ns"] > t_ns:
            break
        if t_ns < s["start_ns"] + s["dur_ns"]:
            best = s
    return best


def idle_by_overlap(
    gaps: List[Tuple[float, float]], spans: List[Dict[str, Any]]
) -> Dict[str, float]:
    """Idle nanoseconds by the innermost span they overlap: each gap
    (``(start_ns, duration_ns)``) is cut at every span boundary inside it and
    each piece goes to the span open there that started last (``NO_SPAN``
    where none is).  The midpoint rule of table (ii) bills a whole gap to one
    span; a gap that runs from a pull's return over the host's work to the
    next program's first operation is then named after whatever lies in its
    middle.  ``spans`` sorted by start, of one thread."""
    bounds = sorted({s["start_ns"] for s in spans} | {s["start_ns"] + s["dur_ns"] for s in spans})
    out: Dict[str, float] = {}
    for start, dur in gaps:
        cuts = [start, *(b for b in bounds if start < b < start + dur), start + dur]
        for lo, hi in zip(cuts, cuts[1:]):
            sp = innermost_span(spans, (lo + hi) / 2)
            name = sp["name"] if sp is not None else NO_SPAN
            out[name] = out.get(name, 0.0) + hi - lo
    return out


def round_host_only(spans: List[Dict[str, Any]]) -> List[Tuple[Dict[str, Any], List[Dict[str, Any]], float]]:
    """Per ``round`` span: the spans of its thread that lie inside it, and its
    host-only nanoseconds — its duration less the interval from its first
    ``dispatch`` or ``prefill_chunk`` start to its last ``pull`` end."""
    out = []
    for r in (s for s in spans if s["name"] == "round"):
        lo, hi = r["start_ns"], r["start_ns"] + r["dur_ns"]
        inside = [
            s for s in spans
            if s is not r and s["thread"] == r["thread"]
            and lo <= s["start_ns"] and s["start_ns"] + s["dur_ns"] <= hi
        ]
        starts = [s["start_ns"] for s in inside if s["name"] in ("dispatch", "prefill_chunk")]
        ends = [s["start_ns"] + s["dur_ns"] for s in inside if s["name"] == "pull"]
        if starts and ends:
            out.append((r, inside, r["dur_ns"] - (max(ends) - min(starts))))
    return out


def xplane_report(path: str, out=sys.stdout, max_gaps: int = 40) -> int:
    device_ops, device_programs, spans = _load_profile(path)
    if not device_ops:
        out.write(f"{path}: no device plane with an {XLA_OPS_LINE!r} line\n")
        return 1
    rounds = round_host_only(spans)
    # what "per round" divides by: serving rounds, else the trainer's updates
    frame = "round" if rounds else "update_step"
    n_frames = len(rounds) or sum(s["name"] == frame for s in spans)
    out.write(
        f"{path}\n{len(device_ops)} device plane(s), {len(spans)} host spans, "
        f"{n_frames} whole {frame} spans\n"
    )
    for plane, ops in sorted(device_ops.items()):
        gaps = idle_gaps(ops)
        t0 = min(start for start, _ in ops)
        t1 = max(start + dur for start, dur in ops)
        all_idle = sum(d for _, d in idle_gaps(ops, 0.0))
        by_name: Dict[str, List[float]] = {}
        rows = []
        for start, dur in gaps:
            sp = innermost_span(spans, start + dur / 2)
            name = sp["name"] if sp is not None else NO_SPAN
            by_name.setdefault(name, []).append(dur)
            rows.append((start, dur, name))
        total = sum(d for _, d in gaps)
        out.write(
            f"\n{plane}: {(t1 - t0) / 1e9:.3f} s from first to last operation, idle "
            f"{all_idle / 1e9:.3f} s, of it {total / 1e9:.3f} s in {len(gaps)} gaps of "
            f"{MIN_GAP_NS / 1e6:g} ms or more\n"
        )
        out.write(f"  (i) gaps, longest first ({min(len(rows), max_gaps)} of {len(rows)})\n")
        out.write(f"  {'at_ms':>10} {'gap_ms':>9}  innermost host span at the midpoint\n")
        for start, dur, name in sorted(rows, key=lambda r: -r[1])[:max_gaps]:
            out.write(f"  {(start - t0) / 1e6:>10.2f} {dur / 1e6:>9.3f}  {name}\n")
        out.write(f"  (ii) gap seconds by span, and milliseconds per {frame} ({n_frames} whole ones)\n")
        out.write(f"  {'span':<20} {'gaps':>5} {'total_s':>9} {'share':>7} {'ms/' + frame:>15}\n")
        for name, durs in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
            out.write(
                f"  {name:<20} {len(durs):>5} {sum(durs) / 1e9:>9.4f} "
                f"{100.0 * sum(durs) / max(total, 1.0):>6.1f}% {sum(durs) / 1e6 / max(n_frames, 1):>15.2f}\n"
            )
        named = sum(sum(v) for k, v in by_name.items() if k != NO_SPAN)
        out.write(
            f"  put down to a named span: {100.0 * named / max(total, 1.0):.1f}%; "
            f"to {NO_SPAN}: {100.0 * (total - named) / max(total, 1.0):.1f}%\n"
        )
        if rounds:
            # within the whole rounds' extent, on their thread: what the
            # scheduler's own count (round.host_gap_ms) can be held against
            thread = rounds[0][0]["thread"]
            lo = min(r["start_ns"] for r, _, _ in rounds)
            hi = max(r["start_ns"] + r["dur_ns"] for r, _, _ in rounds)
            clipped = [
                (max(s, lo), min(s + d, hi) - max(s, lo))
                for s, d in idle_gaps(ops, 0.0) if s + d > lo and s < hi
            ]
            overlap = idle_by_overlap(clipped, [s for s in spans if s["thread"] == thread])
            out.write(
                f"  (ii') all idle time inside the whole rounds, by the innermost span it overlaps: "
                f"{sum(overlap.values()) / 1e6 / n_frames:.2f} ms/{frame}\n"
            )
            for name, ns in sorted(overlap.items(), key=lambda kv: -kv[1]):
                out.write(f"  {name:<20} {ns / 1e6 / n_frames:>15.2f}\n")
    frames = [
        (s["start_ns"], s["start_ns"] + s["dur_ns"])
        for s in ([r for r, _, _ in rounds] or [s for s in spans if s["name"] == frame])
    ]
    for plane, programs in sorted(device_programs.items()):
        out.write(f"\n{plane}: {len(programs)} executions of XLA programs\n")
        if frames:
            per_frame = programs_per_frame(programs, frames)
            out.write(
                f"  (iii) programs started inside the {len(frames)} whole {frame} spans: "
                f"{sum(per_frame.values()):.1f} per {frame}\n"
            )
            for name, n in sorted(per_frame.items(), key=lambda kv: -kv[1]):
                out.write(f"  {name:<40} {n:>8.2f}\n")
    if rounds:
        host = [ns for _, _, ns in rounds]
        out.write(
            f"\nrounds: mean {sum(r['dur_ns'] for r, _, _ in rounds) / len(rounds) / 1e6:.2f} ms, "
            f"host-only mean {sum(host) / len(host) / 1e6:.2f} ms "
            f"(min {min(host) / 1e6:.2f}, max {max(host) / 1e6:.2f})\n"
        )
        per_child: Dict[str, float] = {}
        for _, inside, _ in rounds:
            for s in inside:
                per_child[s["name"]] = per_child.get(s["name"], 0.0) + s["dur_ns"]
        out.write(f"  {'span in a round':<20} {'ms/round':>9}\n")
        for name, ns in sorted(per_child.items(), key=lambda kv: -kv[1]):
            out.write(f"  {name:<20} {ns / 1e6 / len(rounds):>9.2f}\n")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--xplane", metavar="FILE.xplane.pb",
        help="report a jax.profiler profile: device idle gaps by host span",
    )
    ap.add_argument(
        "paths", nargs="*", metavar="path",
        help="flight_*.json dumps and/or *.jsonl span streams; several paths "
        "are merged into one timeline joined on shared trace ids "
        "(e.g. router_spans_*.jsonl + serve_spans_*.jsonl)",
    )
    ap.add_argument("--trace", help="render only this trace id")
    ap.add_argument(
        "--max-traces", type=int, default=3,
        help="without --trace: how many of the most recent traces to render",
    )
    ap.add_argument("--chrome", help="also export Chrome trace-event JSON here")
    args = ap.parse_args(argv)
    if args.xplane:
        return xplane_report(args.xplane)
    if not args.paths:
        ap.error("give span files, or --xplane FILE.xplane.pb")

    if len(args.paths) == 1:
        spans, events, header = load(args.paths[0])
        if header.get("reason"):
            sys.stdout.write(
                f"flight dump: reason={header['reason']} pid={header.get('pid')} "
                f"dropped_spans={header.get('dropped_spans', 0)}\n\n"
            )
    else:
        streams = []
        for path in args.paths:
            s, e, _ = load(path)
            streams.append((Path(path).name, s, e))
        spans, events = merge_streams(streams)
        sys.stdout.write(
            f"merged {len(args.paths)} streams: "
            + " ".join(name for name, _, _ in streams) + "\n\n"
        )
    if not spans and not events:
        print("empty trace")
        return 1

    if args.trace:
        trace_ids = [args.trace]
    else:
        seen: List[str] = []  # insertion order == recording order
        for s in spans:
            tid = s.get("trace_id")
            if tid and tid not in seen:
                seen.append(tid)
        trace_ids = seen[-args.max_traces:]
    for tid in trace_ids:
        print_tree(spans, tid)
    phase_summary(spans)

    if args.chrome:
        if len(args.paths) == 1:
            trace_events = chrome_trace_events(spans, events)
        else:
            # one Chrome process per source stream, labelled with the file
            # it came from, so Perfetto shows router and replicas as
            # separate swim lanes on the shared wall-clock axis
            trace_events = []
            for i, (name, _, _) in enumerate(streams):
                pid = i + 1
                trace_events.extend(
                    chrome_trace_events(
                        [s for s in spans if s.get("_pid") == pid],
                        [e for e in events if e.get("_pid") == pid],
                        pid=pid,
                    )
                )
                trace_events.append(
                    {"name": "process_name", "ph": "M", "pid": pid,
                     "args": {"name": name}}
                )
        with open(args.chrome, "w") as fh:
            json.dump({"traceEvents": trace_events}, fh)
        print(f"\nchrome trace written to {args.chrome}")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        # downstream closed early (e.g. `| head`, `| grep -q`): not an error.
        # Point stdout at devnull so the interpreter's exit-time flush of the
        # dead pipe can't raise a second time.
        import os

        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
