#!/usr/bin/env python3
"""Readings of the faults an ``afmoe`` forward can have, on the chip, at the
cell's own size: what ``served_logit_gap`` and its mean read when the
reference stands in the program's place with one part of the mathematics
wrong (``benchmark/reference/afmoe.FAULTS``: no attention gate, no q/k norms,
no rotary in the window layers, rotary added to the full layer, no
post-attention norm, no shared expert, no route scale, no selection bias, no
embedding multiplier, a window one short) or one precision down (fp8, and
bf16 for the noise floor).  The cell's limits are set from these beside the
program's own readings.

    python3 benchmark/tools/faults_afmoe.py --workload <name> --seeds 1,2,3 [--control-seeds 1,2] \\
        [--control-requests 2] [--seconds 5] [--out file.jsonl]

For every seed the program's own readings over the cell's ``checked_requests``;
for the control seeds also the controls and every fault, over the first
``--control-requests`` of the same sample (the longest request first): a
control costs two reference passes.

The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-requests", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmark.drivers import serve_afmoe
    from benchmark.reference.afmoe import FAULTS

    cell = harness.Cell(args.workload)
    harness.setup_jax_cache()
    harness.device_info(cell.chips)
    compiles = harness.CompileCounter()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = serve_afmoe.run(cell, seed, args.seconds, False, compiles)
        row = {"workload": cell.name, "seed": seed, "program": {n: v for n, v, _ in out["check"].rows},
               "reference_s": out["reference_s"], "end_to_end": out["end_to_end"]}
        if seed in controls:
            sample = out["debug"]["sample"][: args.control_requests]
            row["program_on_control_sample"] = serve_afmoe.served_gap(cell, seed, sample)
            for cast in ("fp8", "bf16"):
                row[f"control_{cast}"] = serve_afmoe.served_gap(cell, seed, sample, cast=cast)
            for fault in FAULTS:
                row[f"fault_{fault}"] = serve_afmoe.served_gap(cell, seed, sample, faults=(fault,))
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
