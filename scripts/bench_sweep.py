"""Parameterized on-chip throughput bench — the lever A/B harness.

Thin CLI over relora_tpu.utils.benchlib.run_throughput_bench (the same
measurement loop bench.py uses), with every lever exposed as a flag so each
configuration runs in its own process (the sandbox's remote-compile helper
holds per-process state; a fresh process per config also sidesteps
compile-cache interference when sweeping microbatch).  Prints ONE JSON line
per run.

Usage::

    python scripts/bench_sweep.py --micro-batch 8 --remat --loss-impl dense
    python scripts/bench_sweep.py --micro-batch 16 --loss-impl chunked \
        --logits-dtype bf16 --attn pallas
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WATCHDOG_SECS = int(os.environ.get("BENCH_WATCHDOG_SECS", "900"))


def _watchdog():
    print(json.dumps({"error": f"no result within {WATCHDOG_SECS}s"}))
    sys.stdout.flush()
    os._exit(2)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="llama_1b")
    p.add_argument("--micro-batch", type=int, default=8)
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--remat", action="store_true")
    p.add_argument(
        "--remat-policy", default="full", choices=["full", "dots", "dots_narrow", "dots_all"]
    )
    p.add_argument("--loss-impl", default="dense", choices=["dense", "chunked"])
    p.add_argument("--vocab-chunk", type=int, default=8192)
    p.add_argument("--logits-dtype", default="f32", choices=["f32", "bf16"])
    p.add_argument("--attn", default="auto")
    p.add_argument("--rank", type=int, default=128)
    p.add_argument(
        "--quantize", default="", choices=["", "int8", "nf4"], help="frozen-base storage"
    )
    p.add_argument(
        "--base-dtype", default="", choices=["", "bf16"],
        help="unquantized frozen-base storage dtype (default f32 master)",
    )
    p.add_argument("--dropout", type=float, default=0.1)
    p.add_argument("--prng", default="", help="jax_default_prng_impl override (e.g. rbg)")
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--label", default="")
    p.add_argument(
        "--out",
        default="",
        help="also append the JSON result line to this file (partial results "
        "survive an interrupted sweep)",
    )
    args = p.parse_args()

    if args.prng:
        import jax

        jax.config.update("jax_default_prng_impl", args.prng)

    from relora_tpu.utils.benchlib import run_throughput_bench

    res = run_throughput_bench(
        args.model,
        micro_batch=args.micro_batch,
        grad_accum=args.grad_accum,
        seq=args.seq,
        remat=args.remat,
        remat_policy=args.remat_policy,
        loss_impl=args.loss_impl,
        vocab_chunk=args.vocab_chunk,
        logits_dtype=args.logits_dtype,
        attn=args.attn,
        rank=args.rank,
        quantize=args.quantize or None,
        base_dtype=args.base_dtype or None,
        dropout=args.dropout,
        warmup_steps=args.warmup,
        measure_steps=args.steps,
    )
    line = json.dumps(
        {
            "label": args.label
            or f"{args.model} mb{args.micro_batch} ga{args.grad_accum} seq{args.seq}"
            f" remat={int(args.remat)}:{args.remat_policy}"
            f" {args.loss_impl} {args.logits_dtype}"
            f" attn={args.attn}"
            + (f" quant={args.quantize}" if args.quantize else "")
            + (f" base={args.base_dtype}" if args.base_dtype else ""),
            "tokens_per_sec": res["tokens_per_sec"],
            "mfu": res["mfu"],
            "step_time_s": res["step_time_s"],
            "loss": round(res["loss"], 6),
            "hbm_peak_gb": res.get("hbm_peak_gb"),
            # benchlib floors warmup to 1 step; surface the effective count
            # so a --warmup 0 sweep can't misattribute its measurement
            "warmup_steps_effective": res.get("warmup_steps_effective"),
        }
    )
    print(line)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    timer = threading.Timer(WATCHDOG_SECS, _watchdog)
    timer.daemon = True
    timer.start()
    main()
    timer.cancel()
