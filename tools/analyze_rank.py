"""Rank/SVD analysis of learned weight updates — the paper's core claim.

Systematizes the reference's analysis notebooks (notebooks/05_check_ranks,
06_svd, 08_ranks_before_and_after — SURVEY.md §4): given two checkpoints
(e.g. the warm-start point and the end of ReLoRA training), compute the
singular-value spectrum and effective rank of ΔW for every wrapped linear,
demonstrating that repeated rank-r updates accumulate a high-rank total
update.

Usage::

    python tools/analyze_rank.py --before ckpts/warmup/model_10000 \
        --after ckpts/relora/model_20000 [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def effective_rank(singular_values: np.ndarray, threshold: float = 1e-3) -> int:
    """Number of singular values above threshold * sigma_max."""
    if singular_values.size == 0:
        return 0
    return int((singular_values > threshold * singular_values[0]).sum())


def entropy_rank(singular_values: np.ndarray) -> float:
    """exp(Shannon entropy of the normalized spectrum) — a soft rank."""
    p = singular_values / max(singular_values.sum(), 1e-12)
    p = p[p > 0]
    return float(np.exp(-(p * np.log(p)).sum()))


def delta_spectra(before: dict, after: dict, prefix: str = "") -> dict:
    """Walk two (unstacked or stacked) param trees, SVD every kernel delta."""
    out = {}
    for k in before:
        if k not in after:
            continue
        b, a = before[k], after[k]
        if isinstance(b, dict):
            out.update(delta_spectra(b, a, prefix=f"{prefix}{k}."))
        elif k == "kernel" and getattr(b, "ndim", 0) >= 2:
            delta = np.asarray(a, np.float64) - np.asarray(b, np.float64)
            if delta.ndim == 2:
                deltas = {f"{prefix}kernel": delta}
            else:  # scan-stacked: one entry per layer
                deltas = {
                    f"{prefix}kernel[layer{i}]": delta[i] for i in range(delta.shape[0])
                }
            for name, d in deltas.items():
                s = np.linalg.svd(d, compute_uv=False)
                out[name] = {
                    "shape": list(d.shape),
                    "frobenius": float(np.linalg.norm(d)),
                    "effective_rank": effective_rank(s),
                    "entropy_rank": entropy_rank(s),
                    "top_singular_values": s[:16].tolist(),
                }
    return out


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--before", required=True, help="checkpoint dir (model_N)")
    p.add_argument("--after", required=True)
    p.add_argument("--json", default=None, help="write full report here")
    args = p.parse_args(argv)

    sys.path.insert(0, ".")
    import jax

    # offline tool: host CPU is all we need, and it must not hold a chip
    jax.config.update("jax_platforms", "cpu")
    from relora_tpu.train.checkpoint import restore_params_host

    before = restore_params_host(args.before)
    after = restore_params_host(args.after)
    report = delta_spectra(before, after)

    ranks = [v["effective_rank"] for v in report.values()]
    print(f"analyzed {len(report)} weight deltas")
    if ranks:
        print(f"effective rank of ΔW: min={min(ranks)} median={int(np.median(ranks))} max={max(ranks)}")
    for name, v in sorted(report.items())[:10]:
        print(f"  {name}: shape={v['shape']} eff_rank={v['effective_rank']} |ΔW|={v['frobenius']:.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"full report -> {args.json}")


if __name__ == "__main__":
    try:
        main()
    except BrokenPipeError:
        # stdout piped into `head` that already exited (smoke_test.sh does
        # this); the truncated output is what the reader asked for
        import os

        os.dup2(os.open(os.devnull, os.O_WRONLY), 1)
