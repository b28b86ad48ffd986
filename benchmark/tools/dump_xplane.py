#!/usr/bin/env python3
"""Print what a trace holds: planes, lines, event counts and the events with
most time on each device line. For looking at a trace by hand.

    python3 benchmark/tools/dump_xplane.py <trace dir or .xplane.pb>
"""
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import xplane  # noqa: E402


def main(path):
    from jax.profiler import ProfileData
    if not path.endswith(".pb"):
        path = xplane.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            t0 = min(e.start_ns for e in evs)
            t1 = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{t0 / 1e9:.6f} .. {t1 / 1e9:.6f} s")
            per = defaultdict(lambda: [0.0, 0])
            for e in evs:
                per[e.name][0] += e.duration_ns
                per[e.name][1] += 1
            top = sorted(per.items(), key=lambda x: -x[1][0])[:25]
            for name, (ns, n) in top:
                print(f"      {ns / 1e6:12.3f} ms  x{n:<6d} {name[:110]}")
    tr = xplane.load(path)
    print(list(tr.notes()))
    print(tr.breakdown())


if __name__ == "__main__":
    main(sys.argv[1])
