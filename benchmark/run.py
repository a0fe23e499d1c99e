#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the machine this is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process.  It refuses to start unless JAX finds the cell's TPU chips, warms
up the cell's own shapes (counted as set-up), measures for ``--seconds``, then
checks what the timed path produced against the plain reference, and prints
one JSON object as its last line: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device`` and, last, the numbers compared.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can tell

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool, device: dict, keep_trace: bool = False) -> dict:
    """Everything after the look for the chip; returns the result object."""
    import importlib

    compiles = harness.CompileCounter()
    driver = importlib.import_module(f"benchmark.drivers.{cell.workload['driver']}")
    out = driver.run(cell, seed, seconds, trace, compiles)
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    end_to_end = dict(out["end_to_end"], setup_s=out["t_open"] - T_START)
    if not trace:
        units = {m["name"]: m["unit"] for m in cell.metrics("end_to_end")}
        metrics = {k: {"value": float(v), "unit": units[k]} for k, v in end_to_end.items() if k in units}
        odd = [k for k, m in metrics.items() if m["value"] != m["value"] or m["value"] <= 0]
        if odd:
            raise SystemExit(f"no reading for {odd}: the window of {seconds} s was too short for this cell")
        result = {"metrics": metrics, "device": device}
    else:
        from benchmark.readers import trace as trace_reader

        summary = {}
        xplane = trace_reader.find_xplane(out["trace_dir"]) if out.get("trace_span") else None
        if xplane:
            t0, t1 = out["trace_span"][:2]
            summary = trace_reader.reduce(trace_reader.load_xplane(xplane), t1 - t0)
        if not keep_trace:
            shutil.rmtree(out["trace_dir"], ignore_errors=True)
        obs = dict(
            out["obs"], trace=summary, window_s=out.get("layer_window_s", out["window_s"]), chips=cell.chips,
            peak=harness.peaks_for(device["kind"]) if device["platform"] == "tpu" else None,
        )
        if summary:
            device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result = {"metrics": harness.per_layer_metrics(cell, obs), "device": device}
        if summary:
            result["breakdown"] = trace_reader.breakdown(summary)
    check = out["check"]
    check.print_stderr()
    return {
        "correct": check.correct, "attempted": out["attempted"], "failed": out["failed"],
        **result, "reference_s": out["reference_s"], "compared": check.as_dict(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--keep-trace", action="store_true", help="leave the profiler's files under .bench_work/ (to look at by hand)")
    args = ap.parse_args(argv)
    if not harness.program_present():
        print(f"{harness.ROOT} holds no relora_tpu/ and main.py: nothing to measure", file=sys.stderr)
        return harness.EXIT_NO_PROGRAM
    cell = harness.Cell(args.workload)
    harness.setup_jax_cache()
    device = harness.device_info(cell.chips)
    harness.peaks_for(device["kind"])  # an unknown kind is an error before any work
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device, args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
