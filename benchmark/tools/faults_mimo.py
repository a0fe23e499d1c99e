#!/usr/bin/env python3
"""Readings of the faults a ``mimo_v2`` forward can have, on the chip, at the
cell's own size: what ``served_logit_gap`` reads when the reference stands in
the program's place with one part of the mathematics wrong (a bf16 router,
no sink, no selection bias, no value scale, a window one short) — each has to
read over the cell's limit.  ``tools/readings.py`` gives the program's own
readings and the precision controls; this gives the rest.

    python3 benchmark/tools/faults_mimo.py --workload <name> --seeds 1,2,3 [--control-seeds 1,2] [--seconds 5] [--out file.jsonl]

For every seed the program's own readings (the widest and the mean gap); for
the control seeds also the fp8 and bf16 controls and every fault.

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
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    from benchmark.drivers import serve_mimo
    from benchmark.reference.mimo import FAULTS

    cell = harness.Cell(args.workload)
    harness.setup_jax_cache()
    harness.device_info(cell.chips)
    compiles = harness.CompileCounter()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = serve_mimo.run(cell, seed, args.seconds, False, compiles)
        row = {"workload": cell.name, "seed": seed, "program": {n: v for n, v, _ in out["check"].rows},
               "end_to_end": out["end_to_end"]}
        if seed in controls:
            sample = out["debug"]["sample"]
            for cast in ("fp8", "bf16"):
                row[f"control_{cast}"] = serve_mimo.served_gap(cell, seed, sample, cast=cast)
            for fault in FAULTS:
                row[f"fault_{fault}"] = serve_mimo.served_gap(cell, seed, sample, faults=(fault,))
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
