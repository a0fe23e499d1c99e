#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and the names that take most time.

    python3 benchmark/tools/trace_dump.py <trace_dir> [out.json]

With ``out.json`` it also writes the device planes' program events and the
first operations as plain events, the form ``readers/trace.py`` reduces.
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main() -> int:
    from jax.profiler import ProfileData

    from benchmark.readers import trace

    path = trace.find_xplane(sys.argv[1])
    if path is None:
        print(f"no .xplane.pb under {sys.argv[1]}", file=sys.stderr)
        return 1
    print(path, os.path.getsize(path), "bytes")
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            total = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
            print(f"  line {line.name!r}: {len(events)} events")
            if plane.name.startswith("/device:"):
                for name, ns in total.most_common(25):
                    print(f"    {ns / 1e6:10.3f} ms  {name}")
                for e in events[:3]:
                    print("    stats of", e.name, {k: str(v)[:120] for k, v in e.stats})
                customs = [e for e in events if "custom" in e.name.lower() or "pallas" in e.name.lower()][:4]
                for e in customs:
                    print("    custom:", e.name, {k: str(v)[:200] for k, v in e.stats})
    if len(sys.argv) > 2:
        planes = trace.load_xplane(path)
        small = {}
        for plane, lines in planes.items():
            mods = sorted(lines.get(trace.MODULES_LINE, []), key=lambda e: e[1])
            ops = sorted(lines.get(trace.OPS_LINE, []), key=lambda e: e[1])
            if mods:
                t_lo, t_hi = mods[0][1], mods[min(len(mods), 4) - 1][1] + mods[min(len(mods), 4) - 1][2]
                ops = [e for e in ops if t_lo <= e[1] <= t_hi]
                mods = mods[:4]
            small[plane] = {trace.MODULES_LINE: mods, trace.OPS_LINE: ops}
        with open(sys.argv[2], "w") as f:
            json.dump(small, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
