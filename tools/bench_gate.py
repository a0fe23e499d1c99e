#!/usr/bin/env python
"""Thresholded regression gate over the committed BENCH_* trajectory.

Twelve rules, each skipped gracefully when its input files are absent:

1. **train tok/s** (``BENCH_r*.json``): the latest round with a real
   measurement (``parsed.value > 0`` — watchdog rounds report 0 and are
   ignored, as are stale replays with ``detail.stale``) must be within
   ``--tolerance`` (default 10%) of the best previous real round.  A
   fresh regression shows up as the newest value dropping below
   ``best * (1 - tolerance)``.
2. **MFU floor** (``BENCH_r*.json``): the newest non-stale on-TPU round
   must report ``detail.mfu >= --mfu-floor`` (default 0.25, or the
   ``mfu_floor`` key in the baselines file).  Skipped for stale replays
   and CPU rounds — off-TPU numbers say nothing about chip utilization.
3. **serving latency** (``BENCH_http.json`` vs ``tools/bench_baselines.json``):
   per-level ``ttft_p95_ms`` / ``tpot_p95_ms`` must stay under the committed
   caps (baseline p95 x (1 + tolerance), pre-expanded in the baselines file
   with generous CPU-noise margins).
4. **router failover** (``BENCH_http.json`` ``detail.router``): zero hung
   requests under a mid-run replica SIGKILL, the killed replica restarted,
   and clean/kill ``ttft_p95_ms`` under the committed router caps.
5. **obs overhead** (``BENCH_obs.json``): ``detail.within_budget`` must be
   true — the span tracer's measured overhead stayed inside its budget_pct.
6. **attention kernel** (``BENCH_attn.json``): on TPU the fused paged-decode
   arm must not lose to the naive gather arm by more than ``--tolerance``
   on any decode bucket, and the roofline's ``model_choice`` must agree
   with ``measured_best`` on the arm family.  Skipped entirely when the
   artifact was recorded in interpreter mode (``detail.is_interpret`` —
   off-TPU the pallas arm runs the pallas interpreter, a correctness
   record whose timings carry no performance signal).
7. **speculative decoding** (``BENCH_http.json`` ``detail.spec_runs``): on
   TPU every ngram sweep level must hold its accept rate at or above the
   committed ``spec_accept_rate_floor`` and its effective tok/s within
   ``--tolerance`` of the non-speculative "off" level.  Skipped off-TPU —
   CPU timings and random-token bench prompts carry no speculation signal.
8. **packed step** (``BENCH_http.json`` ``detail.packed_run``): the packed
   token-budget run must issue exactly one model dispatch per scheduler
   round, and on TPU its peak-level ``ttft_p95_ms`` must stay within
   ``--tolerance`` of the sequential headline — packing decode and prefill
   into one forward must not starve first tokens.  The latency half is
   skipped off-TPU.
9. **autoscale** (``BENCH_http.json`` ``detail.autoscale_run``): across the
   1→2→1 elastic resize driven by ``bench.py --mode autoscale``, zero
   requests may be dropped (rejected-with-429 is typed backpressure and
   allowed; vanishing mid-stream is not), the burst must have scaled the
   fleet up, and the quiet tail must have scaled it back down.  Structural
   — counts requests and replicas, not time — so it runs everywhere.
10. **grouped LoRA** (``BENCH_lora.json`` ``detail.grouped_buckets``): on TPU
   the grouped multi-tenant arm on a degenerate single-adapter batch
   (``distinct_adapters == 1``) must stay within ``--tolerance`` of the
   single-adapter fused arm on the same (B, K, N, r) bucket — the grouped
   kernel's scalar-prefetch indirection must be ~free when every row hits
   one slot.  Skipped when the artifact was recorded in interpreter mode
   (``detail.fused_is_interpret``).
11. **disaggregated handoff** (``BENCH_http.json`` ``detail.disagg_run``):
   the prefill→decode scheduler pair draining the long+short mix through
   the migration wire must finish token-identical to the single mixed
   scheduler with zero dropped requests on every kv_dtype arm, and the
   int8 arm's migrated bytes must be at most 0.3x the bf16 arm's — the
   quantized page payload is the whole point of migrating int8 pools.
   Structural — counts and parity, not time — so it runs everywhere.
12. **compression** (``BENCH_compress.json``): the prune-retrain ladder must
   cover at least the committed ``compress.min_levels`` sparsity levels, every
   level must report its GLUE score and draft accept rate, greedy ``--spec
   model`` output must be token-identical to the non-speculative run at every
   sparsity level, and the lightest level's accept rate must clear
   ``compress.accept_rate_floor`` — a near-dense draft that stops agreeing
   with its own base means the draft KV lockstep or the verify walk broke.
   Structural (parity, counts, deterministic greedy accept math — not wall
   time), so it runs everywhere, off-TPU included.

Exit codes: 0 = all rules pass (or skipped), 1 = regression, 2 = usage error.
``--warn-only`` reports failures but exits 0 — CI uses it off-TPU where the
numbers are load-noisy.

    python tools/bench_gate.py --check
    python tools/bench_gate.py --check --dir /path/to/benches --tolerance 0.15
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

BASELINES_PATH = Path(__file__).resolve().parent / "bench_baselines.json"


def _load(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, json.JSONDecodeError):
        return None


def real_rounds(bench_dir: str) -> List[Tuple[int, float]]:
    """(round_n, tok/s) for every round with a real measurement, sorted by n.
    Watchdog/stalled rounds (value <= 0) carry no signal and are dropped, as
    are stale replays (``detail.stale`` — an outage round re-emitting the
    last on-chip number is provenance, not a fresh measurement: comparing it
    against itself would mask a real regression on the next live round)."""
    rounds = []
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r[0-9]*.json")):
        doc = _load(path)
        if not doc:
            continue
        parsed = doc.get("parsed") or {}
        if (parsed.get("detail") or {}).get("stale"):
            continue
        value = parsed.get("value")
        if isinstance(value, (int, float)) and value > 0:
            rounds.append((int(doc.get("n", 0)), float(value)))
    rounds.sort()
    return rounds


def check_train(bench_dir: str, tolerance: float) -> List[str]:
    rounds = real_rounds(bench_dir)
    if len(rounds) < 2:
        return []  # nothing to compare against yet
    *prev, (latest_n, latest) = rounds
    best_n, best = max(prev, key=lambda r: r[1])
    floor = best * (1.0 - tolerance)
    if latest < floor:
        return [
            f"train tok/s: round {latest_n} = {latest:,.1f} is "
            f"{(1 - latest / best) * 100:.1f}% below best round {best_n} "
            f"({best:,.1f}); floor at {tolerance * 100:.0f}% is {floor:,.1f}"
        ]
    return []


def check_mfu(bench_dir: str, floor: float) -> List[str]:
    """MFU floor over the train rounds: the newest non-stale on-TPU round
    reporting ``detail.mfu`` must meet ``floor``.  Stale replays and CPU
    rounds are skipped — a replayed number or an off-TPU CI run says nothing
    about chip utilization.  The floor is a ratchet guard under the 50%
    north star: it holds the measured band, it is not the target itself."""
    latest: Optional[Tuple[int, float]] = None
    for path in glob.glob(os.path.join(bench_dir, "BENCH_r[0-9]*.json")):
        doc = _load(path)
        if not doc:
            continue
        parsed = doc.get("parsed") or {}
        detail = parsed.get("detail") or {}
        mfu = detail.get("mfu")
        if detail.get("stale") or not isinstance(mfu, (int, float)) or mfu <= 0:
            continue
        if "cpu" in str(detail.get("device", "")).lower():
            continue
        n = int(doc.get("n", 0))
        if latest is None or n > latest[0]:
            latest = (n, float(mfu))
    if latest is None:
        return []
    n, mfu = latest
    if mfu < floor:
        return [
            f"mfu: round {n} measured {mfu * 100:.1f}% MFU, below the "
            f"{floor * 100:.0f}% floor (north star is >= 50%)"
        ]
    return []


def check_http(bench_dir: str, baselines: Optional[Dict[str, Any]]) -> List[str]:
    doc = _load(os.path.join(bench_dir, "BENCH_http.json"))
    if not doc or not baselines:
        return []
    caps = baselines.get("http_p95_caps_ms") or {}
    failures = []
    for level in (doc.get("detail") or {}).get("levels") or []:
        cap = caps.get(str(level.get("offered")))
        if not cap:
            continue
        for key in ("ttft_p95_ms", "tpot_p95_ms"):
            got, limit = level.get(key), cap.get(key)
            if isinstance(got, (int, float)) and isinstance(limit, (int, float)) and got > limit:
                failures.append(
                    f"http {level['offered']}: {key} = {got:.1f}ms exceeds cap {limit:.1f}ms"
                )
    return failures


def check_router(bench_dir: str, baselines: Optional[Dict[str, Any]]) -> List[str]:
    """Multi-replica failover rules over ``detail.router`` in BENCH_http.json
    (present only for ``bench.py --mode serve_load --router`` runs):

    - hung_requests must be 0 — a crash degrades to retried or typed-error,
      never to a client waiting forever;
    - the SIGKILLed replica must have been restarted inside the bench window;
    - per-run ttft_p95_ms must stay under the committed router caps.
    """
    doc = _load(os.path.join(bench_dir, "BENCH_http.json"))
    router = ((doc or {}).get("detail") or {}).get("router")
    if not router:
        return []
    failures = []
    hung = router.get("hung_requests", 0)
    if hung:
        failures.append(
            f"router: {hung} hung request(s) under replica failure — every "
            "accepted request must terminate (finish record or typed error)"
        )
    if router.get("replica0_restarted") is False:
        failures.append("router: SIGKILLed replica was not restarted during the bench")
    caps = (baselines or {}).get("router_p95_caps_ms") or {}
    for run in ("clean", "kill"):
        cap = caps.get(run)
        row = router.get(run) or {}
        if not cap:
            continue
        got, limit = row.get("ttft_p95_ms"), cap.get("ttft_p95_ms")
        if isinstance(got, (int, float)) and isinstance(limit, (int, float)) and got > limit:
            failures.append(
                f"router {run}: ttft_p95_ms = {got:.1f}ms exceeds cap {limit:.1f}ms"
            )
    return failures


def check_obs(bench_dir: str) -> List[str]:
    doc = _load(os.path.join(bench_dir, "BENCH_obs.json"))
    if not doc:
        return []
    detail = doc.get("detail") or {}
    failures = []
    if detail.get("within_budget") is False:
        failures.append(
            f"obs overhead: {doc.get('value')}% of step time exceeds "
            f"budget {detail.get('budget_pct')}%"
        )
    collector = detail.get("collector") or {}
    if collector.get("within_budget") is False:
        failures.append(
            f"fleet collector overhead: {collector.get('overhead_pct')}% "
            f"serving throughput loss exceeds budget "
            f"{collector.get('budget_pct')}% "
            f"(off {collector.get('off_tok_s')} tok/s -> "
            f"on {collector.get('on_tok_s')} tok/s)"
        )
    return failures


def check_attn(bench_dir: str, tolerance: float) -> List[str]:
    doc = _load(os.path.join(bench_dir, "BENCH_attn.json"))
    if not doc:
        return []
    detail = doc.get("detail") or {}
    if detail.get("is_interpret"):
        return []  # interpreter-mode timings carry no performance signal
    failures = []
    for row in detail.get("buckets") or []:
        if row.get("kind") != "decode":
            continue
        shape = f"B={row.get('B')} S_kv={row.get('S_kv')}"
        for tag in ("bf16", "int8"):
            fused = row.get(f"paged_decode_{tag}_ms")
            naive = row.get(f"naive_{tag}_ms")
            if not (isinstance(fused, (int, float)) and isinstance(naive, (int, float))):
                continue
            if fused > naive * (1.0 + tolerance):
                failures.append(
                    f"attn {shape} {tag}: fused paged-decode {fused:.3f}ms is "
                    f"{(fused / naive - 1) * 100:.0f}% slower than naive {naive:.3f}ms"
                )
            choice = row.get(f"model_choice_{tag}")
            best = row.get("measured_best") or ""
            if choice and best and not best.startswith(choice):
                failures.append(
                    f"attn {shape} {tag}: roofline picked {choice} but measured "
                    f"best arm was {best}"
                )
    return failures


def check_spec(
    bench_dir: str, baselines: Optional[Dict[str, Any]], tolerance: float
) -> List[str]:
    """Speculative-decoding rules over ``detail.spec_runs`` in BENCH_http.json
    (present only for paged ``--mode serve_load`` runs with the spec sweep):

    - every ngram level that drafted anything must hold its cumulative accept
      rate at or above the committed ``spec_accept_rate_floor`` — a collapse
      here means the draft source or the verify/accept walk broke, not noise;
    - each ngram level's effective tok/s must not fall below the "off" level
      by more than ``tolerance`` — speculation that loses throughput to its
      own verify overhead is a regression, the roofline said it should win.

    Skipped entirely off-TPU (like ``check_attn``): CPU timings carry no
    throughput signal, and random-token bench prompts make acceptance a
    property of the model's repetition loops, not the feature.
    """
    doc = _load(os.path.join(bench_dir, "BENCH_http.json"))
    detail = (doc or {}).get("detail") or {}
    spec_runs = detail.get("spec_runs") or {}
    if not spec_runs:
        return []
    if "cpu" in str(detail.get("device", "")).lower():
        return []  # off-TPU: no throughput signal, acceptance is prompt noise
    floor = float((baselines or {}).get("spec_accept_rate_floor", 0.0))
    off_tok_s = (spec_runs.get("off") or {}).get("effective_tokens_per_s")
    failures = []
    for level, run in spec_runs.items():
        if run.get("mode") == "off":
            continue
        drafted = run.get("drafted", 0)
        rate = run.get("accept_rate")
        if drafted and isinstance(rate, (int, float)) and rate < floor:
            failures.append(
                f"spec {level}: accept rate {rate:.3f} below floor {floor:.3f} "
                f"({run.get('accepted', 0)}/{drafted} drafted tokens accepted)"
            )
        got = run.get("effective_tokens_per_s")
        if isinstance(got, (int, float)) and isinstance(off_tok_s, (int, float)):
            if got < off_tok_s * (1.0 - tolerance):
                failures.append(
                    f"spec {level}: effective {got:,.1f} tok/s is "
                    f"{(1 - got / off_tok_s) * 100:.0f}% below non-speculative "
                    f"{off_tok_s:,.1f} tok/s (tolerance {tolerance * 100:.0f}%)"
                )
    return failures


def check_compress(bench_dir: str, baselines: Optional[Dict[str, Any]]) -> List[str]:
    """Compression rules over BENCH_compress.json (``bench.py --mode
    compress`` — the prune-retrain ladder from relora_tpu/compress):

    - the ladder must cover at least ``compress.min_levels`` sparsity levels
      (default 3) — one point is a smoke test, not a quality curve;
    - every level must report a numeric ``glue_score`` and draft
      ``accept_rate`` — a level that silently dropped either half measured
      nothing;
    - greedy ``--spec model`` output must be token-identical to the
      non-speculative run at **every** sparsity level — parity is
      architecture math (``spec_verify_draws`` with temperature 0), so any
      divergence means the draft KV lockstep or the verify/accept walk
      broke, never noise;
    - the lightest level's accept rate must clear
      ``compress.accept_rate_floor`` — with the default ladder the lightest
      draft is the unpruned merge of the same weights, so its acceptance is
      near-total by construction and a collapse is a wiring bug.

    Everything here is structural (parity, counts, deterministic greedy
    accept math — not wall time), so unlike ``check_spec`` the rule runs
    off-TPU too.
    """
    doc = _load(os.path.join(bench_dir, "BENCH_compress.json"))
    detail = (doc or {}).get("detail") or {}
    levels = detail.get("levels") or []
    if not levels:
        return []
    caps = (baselines or {}).get("compress") or {}
    failures = []
    min_levels = int(caps.get("min_levels", 3))
    if len(levels) < min_levels:
        failures.append(
            f"compress: only {len(levels)} sparsity level(s) measured — the "
            f"ladder needs at least {min_levels} to be a quality curve"
        )
    for lv in levels:
        tag = f"compress s={lv.get('sparsity')}"
        spec = lv.get("spec") or {}
        if not isinstance(lv.get("glue_score"), (int, float)):
            failures.append(f"{tag}: missing glue_score — the quality half of the ladder")
        if not isinstance(spec.get("accept_rate"), (int, float)):
            failures.append(f"{tag}: missing draft accept_rate — the serving half of the ladder")
        if spec.get("token_parity") is False:
            failures.append(
                f"{tag}: greedy --spec model output diverged from the "
                "non-speculative run — parity is exact math at temperature 0, "
                "so the draft KV lockstep or the verify walk is broken"
            )
    lightest = min(levels, key=lambda lv: lv.get("sparsity", 1.0))
    floor = float(caps.get("accept_rate_floor", 0.0))
    lspec = lightest.get("spec") or {}
    rate = lspec.get("accept_rate")
    if lspec.get("drafted", 0) and isinstance(rate, (int, float)) and rate < floor:
        failures.append(
            f"compress s={lightest.get('sparsity')}: accept rate {rate:.3f} "
            f"below floor {floor:.3f} on the lightest draft "
            f"({lspec.get('accepted', 0)}/{lspec.get('drafted', 0)} drafted "
            "tokens accepted) — a near-dense draft should track its base"
        )
    return failures


def check_packed(bench_dir: str, tolerance: float) -> List[str]:
    """Packed-step rule over ``detail.packed_run`` in BENCH_http.json
    (present for paged ``--mode serve_load`` runs unless
    ``BENCH_HTTP_PACKED_STEP=0``):

    - the packed run's peak-level ``ttft_p95_ms`` must stay within
      ``tolerance`` of the sequential headline's peak level — token-budget
      scheduling exists to cut dispatch overhead, not to starve first
      tokens behind decode work;
    - the packed run must actually pack: ``dispatches_per_round`` must be
      1.0 (one model dispatch per scheduler round is the whole point).

    The latency comparison is skipped off-TPU (like ``check_attn``): CPU
    wall times carry no performance signal.  The dispatches-per-round
    structural rule runs everywhere — it counts calls, not time.
    """
    doc = _load(os.path.join(bench_dir, "BENCH_http.json"))
    detail = (doc or {}).get("detail") or {}
    packed = detail.get("packed_run") or {}
    if not packed:
        return []
    failures = []
    dpr = (packed.get("dispatch") or {}).get("dispatches_per_round")
    if isinstance(dpr, (int, float)) and dpr > 1.0:
        failures.append(
            f"packed: {dpr:.2f} model dispatches per round — the packed "
            "scheduler must issue exactly one dispatch per round"
        )
    if "cpu" in str(detail.get("device", "")).lower():
        return failures  # off-TPU: no latency signal
    levels = detail.get("levels") or []
    seq_peak = max(
        (lv for lv in levels if isinstance(lv.get("ttft_p95_ms"), (int, float))),
        key=lambda lv: lv.get("throughput_tokens_per_s", 0),
        default=None,
    )
    got = packed.get("ttft_p95_ms_at_peak")
    base = seq_peak.get("ttft_p95_ms") if seq_peak else None
    if isinstance(got, (int, float)) and isinstance(base, (int, float)):
        if got > base * (1.0 + tolerance):
            failures.append(
                f"packed: ttft_p95_ms {got:.1f}ms at peak is "
                f"{(got / base - 1) * 100:.0f}% above the sequential headline "
                f"{base:.1f}ms (tolerance {tolerance * 100:.0f}%)"
            )
    return failures


def check_autoscale(bench_dir: str) -> List[str]:
    """Elastic-fleet rules over ``detail.autoscale_run`` in BENCH_http.json
    (present only for ``bench.py --mode autoscale`` runs):

    - ``dropped_requests`` must be 0 — a scale-up spawn, a warming replica,
      or a scale-down drain must never lose an accepted request (429
      rejections are typed backpressure and do not count);
    - the burst phase must have scaled the fleet up (``scaled_up``), and the
      quiet tail must have brought it back to the floor (``scaled_down``) —
      an autoscaler that never moves is not measuring anything.

    Structural (counts, not wall time), so it runs off-TPU too.
    """
    doc = _load(os.path.join(bench_dir, "BENCH_http.json"))
    run = ((doc or {}).get("detail") or {}).get("autoscale_run")
    if not run:
        return []
    failures = []
    dropped = run.get("dropped_requests", 0)
    if dropped:
        failures.append(
            f"autoscale: {dropped} dropped request(s) across the 1->2->1 "
            "resize — every accepted request must terminate (finish record "
            "or typed error), through spawn, warmup, and drain alike"
        )
    if run.get("scaled_up") is False:
        failures.append(
            "autoscale: the burst phase never scaled the fleet up "
            f"(max_replicas_seen={run.get('max_replicas_seen')})"
        )
    if run.get("scaled_down") is False:
        failures.append(
            "autoscale: the quiet tail never scaled the fleet back down "
            f"(final_replicas={run.get('final_replicas')})"
        )
    return failures


def check_disagg(bench_dir: str) -> List[str]:
    """Disaggregated-handoff rules over ``detail.disagg_run`` in
    BENCH_http.json (present for paged serve_load runs):

    - every kv_dtype arm must finish **token-identical** to the single
      mixed-scheduler baseline — migrating a page run across the wire must
      not perturb a single sampled token;
    - ``dropped_requests`` must be 0 on every arm — a handoff that cannot
      land fails open to donor-local decode, it never loses the request;
    - ``migrated_bytes_ratio_int8_vs_bf16`` must be <= 0.3 — the int8 pool
      ships quantized payloads + per-page scales, so its wire bytes must
      come in well under half the bf16 arm's.

    Structural (parity and byte counts, not wall time), so it runs
    off-TPU too.
    """
    doc = _load(os.path.join(bench_dir, "BENCH_http.json"))
    run = ((doc or {}).get("detail") or {}).get("disagg_run")
    if not run:
        return []
    failures = []
    for dtype, arm in (run.get("runs") or {}).items():
        if arm.get("token_parity") is not True:
            failures.append(
                f"disagg[{dtype}]: prefill->decode drain is not "
                "token-identical to the single mixed scheduler — migration "
                "must preserve the (uid, token_index) sampling stream exactly"
            )
        dropped = arm.get("dropped_requests", 0)
        if dropped:
            failures.append(
                f"disagg[{dtype}]: {dropped} dropped request(s) — a failed "
                "handoff must fail open to local decode, never vanish"
            )
    ratio = run.get("migrated_bytes_ratio_int8_vs_bf16")
    if ratio is None:
        failures.append(
            "disagg: no migrated-bytes ratio recorded (bf16 arm migrated "
            "zero bytes?) — the int8-vs-bf16 comparison needs both arms"
        )
    elif ratio > 0.3:
        failures.append(
            f"disagg: int8 migrated-bytes ratio {ratio:.3f} > 0.3x bf16 — "
            "the quantized page payload is not paying for itself on the wire"
        )
    return failures


def check_grouped_lora(bench_dir: str, tolerance: float) -> List[str]:
    """Grouped multi-tenant LoRA rule over ``detail.grouped_buckets`` in
    BENCH_lora.json: with every row on one adapter (G=1), the grouped
    scalar-prefetch kernel must match the single-adapter fused kernel within
    ``tolerance`` on the same shape — otherwise multi-tenancy taxes
    single-tenant traffic.  Skipped off-TPU (interpreter timings)."""
    doc = _load(os.path.join(bench_dir, "BENCH_lora.json"))
    detail = (doc or {}).get("detail") or {}
    grouped = detail.get("grouped_buckets") or []
    if not grouped or detail.get("fused_is_interpret"):
        return []
    fused_by_shape = {
        (row.get("M"), row.get("K"), row.get("N"), row.get("r")): row.get("fused_ms")
        for row in detail.get("buckets") or []
    }
    failures = []
    for row in grouped:
        if row.get("distinct_adapters") != 1:
            continue
        shape = (row.get("B"), row.get("K"), row.get("N"), row.get("r"))
        fused = fused_by_shape.get(shape)
        got = row.get("grouped_ms")
        if not (isinstance(got, (int, float)) and isinstance(fused, (int, float))):
            continue
        if got > fused * (1.0 + tolerance):
            failures.append(
                f"grouped lora B={shape[0]} K={shape[1]} N={shape[2]} r={shape[3]}: "
                f"grouped arm {got:.3f}ms is {(got / fused - 1) * 100:.0f}% slower "
                f"than single-adapter fused {fused:.3f}ms on a G=1 batch"
            )
    return failures


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="store_true", help="run the gate (the only mode)")
    ap.add_argument(
        "--dir",
        default=str(Path(__file__).resolve().parents[1]),
        help="directory holding BENCH_*.json (default: repo root)",
    )
    ap.add_argument(
        "--tolerance",
        type=float,
        default=0.10,
        help="allowed fractional drop in train tok/s vs the best previous round",
    )
    ap.add_argument(
        "--baselines",
        default=str(BASELINES_PATH),
        help="serving-latency caps JSON ('' disables the http rule)",
    )
    ap.add_argument(
        "--mfu-floor",
        type=float,
        default=None,
        help="minimum MFU for the newest non-stale on-TPU round "
        "(default: baselines 'mfu_floor', else 0.25; 0 disables)",
    )
    ap.add_argument(
        "--warn-only",
        action="store_true",
        help="report regressions but exit 0 (off-TPU CI, where numbers are noisy)",
    )
    args = ap.parse_args(argv)
    if not args.check:
        ap.print_help()
        return 2

    baselines = _load(args.baselines) if args.baselines else None
    mfu_floor = args.mfu_floor
    if mfu_floor is None:
        mfu_floor = float((baselines or {}).get("mfu_floor", 0.25))
    failures = (
        check_train(args.dir, args.tolerance)
        + (check_mfu(args.dir, mfu_floor) if mfu_floor > 0 else [])
        + check_http(args.dir, baselines)
        + check_router(args.dir, baselines)
        + check_obs(args.dir)
        + check_attn(args.dir, args.tolerance)
        + check_spec(args.dir, baselines, args.tolerance)
        + check_packed(args.dir, args.tolerance)
        + check_autoscale(args.dir)
        + check_grouped_lora(args.dir, args.tolerance)
        + check_disagg(args.dir)
        + check_compress(args.dir, baselines)
    )

    rounds = real_rounds(args.dir)
    traj = " -> ".join(f"r{n}:{v:,.0f}" for n, v in rounds) or "no real rounds"
    print(f"bench gate over {args.dir}  (train trajectory: {traj})")
    if failures:
        for f in failures:
            print(f"  REGRESSION: {f}")
        if args.warn_only:
            print("bench gate: FAILURES above (warn-only: exit 0)")
            return 0
        print("bench gate: FAIL")
        return 1
    print("bench gate: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
