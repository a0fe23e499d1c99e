#!/usr/bin/env python
"""One performance-attribution report: metrics.jsonl + traces.

Joins the telemetry streams the obs layer produces into the answer to
"where is the MFU going":

1. **MFU-gap waterfall** — the trainer's per-flush ``mfu_gap/*`` records:
   data_fetch / dispatch / compute / host shares of wall time (they sum to
   ~100% by construction), averaged over the run.
2. **HBM plan** — ``memory_plan`` events: the per-pytree breakdown (params /
   opt_state), XLA's static plan for the compiled train step, and the
   plan-vs-live-peak reconciliation where the backend keeps allocator stats.
3. **Compile telemetry** — ``compile`` events: per-function compile counts,
   expected vs steady-state retraces (the number that should be zero), and
   the signature diff of any retrace.
4. **Serving utilization** — ``serve/batch_fill`` and prefill-stall share
   when the run dir came from the scheduler; paged runs add KV-pool pressure
   (``serve/kv_pages_used``/``free``), prefix-cache hit rate, the
   chunked-prefill padding share, and dispatch economics (dispatches per
   round, tokens per dispatch, packed-token utilization).
5. **Span phases** — p50/p95 per phase from a ``train_spans.jsonl`` stream
   (``--traces``, or auto-detected next to the run dir).

    python tools/perf_report.py ckpts/run
    python tools/perf_report.py ckpts/run --traces traces/train_spans.jsonl
    python tools/perf_report.py ckpts/run --assert-no-retraces   # CI gate
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

# runnable from any cwd without an installed package
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

BAR_WIDTH = 40


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    records = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail line from a killed writer
    return records


def fmt_bytes(n: Optional[float]) -> str:
    if n is None:
        return "n/a"
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if abs(n) >= div:
            return f"{n / div:.2f} {unit}"
    return f"{int(n)} B"


def percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def mean(vals: List[float]) -> float:
    return sum(vals) / len(vals) if vals else 0.0


def print_waterfall(records: List[Dict[str, Any]], out) -> bool:
    gaps = [r for r in records if "mfu_gap/wall_s" in r]
    if not gaps:
        out.write("\nMFU-gap waterfall: no mfu_gap records in metrics.jsonl\n")
        return False
    shares = {
        key: mean([g.get(f"mfu_gap/{key}", 0.0) for g in gaps])
        for key in ("data_fetch", "dispatch", "compute", "comms", "host")
    }
    total_wall = sum(g["mfu_gap/wall_s"] for g in gaps)
    n_steps = sum(int(g.get("mfu_gap/window_steps", 0)) for g in gaps)
    out.write(
        f"\nMFU-gap waterfall  ({len(gaps)} windows, {n_steps} steps, "
        f"{total_wall:.1f}s wall)\n"
    )
    for key, share in shares.items():
        bar = "#" * max(0, round(share * BAR_WIDTH))
        out.write(f"  {key:<12} {share * 100:6.1f}%  {bar}\n")
    out.write(f"  {'sum':<12} {sum(shares.values()) * 100:6.1f}%\n")
    return True


def print_memory(records: List[Dict[str, Any]], out) -> None:
    plans = [r for r in records if r.get("_event") == "memory_plan"]
    if not plans:
        out.write("\nHBM plan: no memory_plan events\n")
        return
    out.write("\nHBM plan\n")
    for plan in plans:
        if plan.get("source") == "pytree":
            out.write("  per-pytree (resident state):\n")
            for key in sorted(plan):
                if key.endswith("_bytes") and not key.startswith("live_"):
                    name = key[: -len("_bytes")]
                    out.write(f"    {name:<12} {fmt_bytes(plan[key]):>12}\n")
        else:
            out.write(f"  XLA static plan ({plan.get('source', '?')}):\n")
            for key in (
                "argument_bytes",
                "output_bytes",
                "temp_bytes",
                "alias_bytes",
                "generated_code_bytes",
                "plan_total_bytes",
            ):
                if key in plan:
                    name = key[: -len("_bytes")]
                    out.write(f"    {name:<16} {fmt_bytes(plan[key]):>12}\n")
            if plan.get("live_peak_bytes") is not None:
                out.write(
                    f"    live peak        {fmt_bytes(plan['live_peak_bytes']):>12}"
                    f"  (live/plan = {plan.get('live_vs_plan')})\n"
                )
            else:
                out.write("    live peak                 n/a  (backend keeps no allocator stats)\n")


def print_compiles(records: List[Dict[str, Any]], out) -> int:
    compiles = [r for r in records if r.get("_event") == "compile"]
    gaps = [r for r in records if "compile/steady_state_retraces" in r]
    retraces = [c for c in compiles if not c.get("expected")]
    n_retraces = len(retraces)
    if gaps:  # the counter in the last record is authoritative for the run
        n_retraces = max(n_retraces, int(gaps[-1]["compile/steady_state_retraces"]))
    out.write("\nCompile telemetry\n")
    if compiles:
        by_fn: Dict[str, List[Dict[str, Any]]] = {}
        for c in compiles:
            by_fn.setdefault(c.get("fn", "?"), []).append(c)
        out.write(f"  {'fn':<16} {'compiles':>8} {'expected':>9} {'total_s':>9}\n")
        for fn, evs in sorted(by_fn.items()):
            out.write(
                f"  {fn:<16} {len(evs):>8} {sum(bool(e.get('expected')) for e in evs):>9} "
                f"{sum(e.get('duration_s', 0.0) for e in evs):>9.2f}\n"
            )
        for c in retraces:
            out.write(f"  RETRACE {c.get('fn')}: {'; '.join(c.get('changed') or [])}\n")
    else:
        out.write("  no compile events recorded\n")
    out.write(f"  steady-state retraces: {n_retraces}\n")
    return n_retraces


def print_train_summary(records: List[Dict[str, Any]], out) -> None:
    steps = [r for r in records if "loss" in r and "update_step" in r]
    if not steps:
        return
    mfus = [r["mfu"] for r in steps if isinstance(r.get("mfu"), (int, float))]
    toks = [
        r["throughput_tokens"]
        for r in steps
        if isinstance(r.get("throughput_tokens"), (int, float))
    ]
    out.write(
        f"\nTraining  ({len(steps)} updates)  loss {steps[-1]['loss']:.4f}"
        f"  mean mfu {mean(mfus):.4f}  mean tok/s {mean(toks):.1f}\n"
    )


def print_serving(records: List[Dict[str, Any]], out) -> None:
    steps = [r for r in records if "serve/batch_fill" in r]
    if not steps:
        return
    fills = [r["serve/batch_fill"] for r in steps]
    stalls = [r.get("serve/prefill_stall_share", 0.0) for r in steps]
    out.write(
        f"\nServing utilization  ({len(steps)} decode steps)\n"
        f"  batch fill      mean {mean(fills) * 100:5.1f}%  min {min(fills) * 100:5.1f}%"
        f"  max {max(fills) * 100:5.1f}%\n"
        f"  prefill stall   mean {mean(stalls) * 100:5.1f}% of step time\n"
    )
    # dispatch economics: the ratios are cumulative-over-the-run gauges, so
    # the last record carries the run's answer (1.00/round = fully packed)
    disp_steps = [r for r in steps if "serve/dispatches_per_round" in r]
    if disp_steps:
        last = disp_steps[-1]
        out.write(
            f"  dispatches      {last['serve/dispatches_per_round']:.2f} per round"
            f"  {last.get('serve/tokens_per_dispatch', 0.0):.1f} tokens each"
            f"  ({last.get('serve/packed_token_utilization', 0.0) * 100:.1f}% real)\n"
        )
    _print_adapters(steps, out)
    # paged-KV pool pressure (PagedContinuousBatchingScheduler runs only)
    paged_steps = [r for r in steps if "serve/kv_pages_used" in r]
    if not paged_steps:
        return
    used = [r["serve/kv_pages_used"] for r in paged_steps]
    free = [r["serve/kv_pages_free"] for r in paged_steps]
    total = used[-1] + free[-1]
    pads = [r.get("serve/prefill_pad_share", 0.0) for r in paged_steps]
    # hit rate is cumulative: the last record is the run's rate
    hit_rate = paged_steps[-1].get("serve/prefix_cache_hit_rate", 0.0)
    out.write(
        f"  kv pages        mean {mean(used):7.1f} used  peak {max(used)} "
        f"of {total}  (min free {min(free)})\n"
        f"  prefix cache    hit rate {hit_rate * 100:5.1f}%\n"
        f"  prefill pad     {pads[-1] * 100:5.1f}% of chunked prefill tokens\n"
    )
    # pool HBM footprint (static per engine; int8 pools report ~1 byte/elem
    # of cache plus per-page scales vs 2 for bf16)
    pool = paged_steps[-1].get("serve/kv_cache_bytes")
    per_tok = paged_steps[-1].get("serve/kv_bytes_per_token")
    if pool is not None:
        out.write(
            f"  kv pool         {fmt_bytes(pool)} resident"
            f"  ({fmt_bytes(per_tok)}/token across layers)\n"
        )
    # speculative decoding (--spec runs only): counters are cumulative, so
    # the last record carries the run totals
    spec_steps = [r for r in paged_steps if "serve/spec_drafted_total" in r]
    if spec_steps:
        last = spec_steps[-1]
        out.write(
            f"  speculative     accept rate {last.get('serve/spec_accept_rate', 0.0) * 100:5.1f}%"
            f"  ({last.get('serve/spec_accepted_total', 0)}/"
            f"{last.get('serve/spec_drafted_total', 0)} drafted tokens accepted)\n"
        )


def _print_adapters(steps: List[Dict[str, Any]], out) -> None:
    """Multi-tenant adapter pressure (--adapter-dir runs only).  Evictions
    are cumulative and hit rate is lifetime, so the last record carries the
    run totals; slot occupancy is a gauge worth averaging."""
    adapter_steps = [r for r in steps if "serve/adapter_slots_used" in r]
    if not adapter_steps:
        return
    used = [r["serve/adapter_slots_used"] for r in adapter_steps]
    last = adapter_steps[-1]
    evictions = last.get("serve/adapter_evictions_total", 0)
    hit_rate = last.get("serve/adapter_hit_rate", 0.0)
    thrash = "  <- slot thrash: raise --adapter-slots" if evictions > 2 * max(used) else ""
    out.write(
        f"  adapter slots   mean {mean(used):5.1f} used  peak {max(used):.0f}\n"
        f"  adapter churn   {evictions:.0f} evictions  hit rate {hit_rate * 100:5.1f}%{thrash}\n"
    )


def print_phases(trace_path: str, out) -> None:
    spans = [s for s in load_jsonl(trace_path) if s.get("dur_s") is not None]
    if not spans:
        return
    by_name: Dict[str, List[float]] = {}
    for s in spans:
        by_name.setdefault(s.get("name", "?"), []).append(s["dur_s"])
    out.write(f"\nSpan phases  ({trace_path})\n")
    out.write(f"  {'phase':<16} {'count':>6} {'p50_ms':>9} {'p95_ms':>9}\n")
    for name, vals in sorted(by_name.items(), key=lambda kv: -sum(kv[1])):
        vals.sort()
        out.write(
            f"  {name:<16} {len(vals):>6} {percentile(vals, 0.5) * 1e3:>9.2f} "
            f"{percentile(vals, 0.95) * 1e3:>9.2f}\n"
        )


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("run_dir", help="run dir containing metrics.jsonl (or the file itself)")
    ap.add_argument("--traces", help="train_spans.jsonl stream (default: autodetect)")
    ap.add_argument(
        "--assert-no-retraces",
        action="store_true",
        help="exit 1 when any steady-state retrace was recorded (smoke/CI)",
    )
    args = ap.parse_args(argv)

    metrics_path = args.run_dir
    if os.path.isdir(metrics_path):
        metrics_path = os.path.join(metrics_path, "metrics.jsonl")
    if not os.path.exists(metrics_path):
        print(f"no metrics.jsonl at {metrics_path}", file=sys.stderr)
        return 2
    records = load_jsonl(metrics_path)
    out = sys.stdout
    out.write(f"perf attribution: {metrics_path}  ({len(records)} records)\n")

    print_train_summary(records, out)
    print_waterfall(records, out)
    print_memory(records, out)
    n_retraces = print_compiles(records, out)
    print_serving(records, out)

    trace_path = args.traces
    if trace_path is None:
        candidate = os.path.join(os.path.dirname(metrics_path), "train_spans.jsonl")
        trace_path = candidate if os.path.exists(candidate) else None
    if trace_path and os.path.exists(trace_path):
        print_phases(trace_path, out)

    if args.assert_no_retraces and n_retraces > 0:
        out.write(f"\nFAIL: {n_retraces} steady-state retraces (expected 0)\n")
        return 1
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:
        # downstream closed early (`| head`): not an error; silence the
        # interpreter's exit-time flush of the dead pipe
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
