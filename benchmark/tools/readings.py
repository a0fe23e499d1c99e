#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip, at the
cell's own size, many seeds in one process.

    python3 benchmark/tools/readings.py --workload <name> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 5] [--out file.jsonl]

For every seed it drives the cell as a run does (a short window) and prints
the numbers compared.  For the control seeds it also puts the reference in
the program's place, one precision below the configuration's (fp8 for bf16),
and for a training cell plants the faults a run can have in the reference
(half of the batch left out).  The benchmark's own runs never run this.
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402


def train_controls(cell, driver, seed, debug) -> dict:
    out = {}
    for name, kw in (("control_fp8", {"cast": "fp8"}), ("control_bf16", {"cast": "bf16"}), ("fault_half_batch", {"half_batch": True})):
        check = harness.Check()
        driver.compare(check, driver.run_reference(cell, seed, debug["batches"], **kw), debug["reference"], cell.workload["limits"])
        out[name] = {n: v for n, v, _ in check.rows}
    return out


def serve_controls(cell, driver, seed, debug) -> dict:
    return {
        f"control_{cast}": {"served_logit_gap": driver.served_gap(cell, seed, debug["sample"], cast=cast)["gap"]}
        for cast in ("fp8", "bf16")
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--controls-only", action="store_true", help="training: the reference and its controls, no program")
    args = ap.parse_args()
    cell = harness.Cell(args.workload)
    harness.setup_jax_cache()
    harness.device_info(cell.chips)
    driver = importlib.import_module(f"benchmark.drivers.{cell.workload['driver']}")
    compiles = harness.CompileCounter()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sink = open(args.out, "a") if args.out else None

    def emit(row: dict) -> None:
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    for seed in (int(s) for s in args.seeds.split(",")):
        if args.controls_only:
            batches = driver.first_batches(cell, seed)
            debug = {"batches": batches, "reference": driver.run_reference(cell, seed, batches)}
            emit({"workload": cell.name, "seed": seed, **train_controls(cell, driver, seed, debug)})
            continue
        out = driver.run(cell, seed, args.seconds, False, compiles)
        row = {"workload": cell.name, "seed": seed, "program": {n: v for n, v, _ in out["check"].rows},
               "reference_s": out["reference_s"], "end_to_end": out["end_to_end"]}
        if seed in controls:
            fn = train_controls if cell.workload["driver"] == "train" else serve_controls
            row.update(fn(cell, driver, seed, out["debug"]))
        emit(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
