#!/usr/bin/env python3
"""Quartile spread of each metric over sets of runs, as the contract takes it:
(q3 - q1) / median with ``statistics.quantiles(values, n=4)``.

    python3 benchmark/tools/spread.py chiprun_out/fitS1.results.jsonl chiprun_out/fitS2.results.jsonl
"""
import json
import statistics
import sys


def main(paths):
    sets = [[json.loads(line) for line in open(p) if line.strip()] for p in paths]
    names = sorted({n for rows in sets for r in rows for n in r["metrics"]})
    for name in names:
        for path, rows in zip(paths, sets):
            vals = [r["metrics"][name]["value"] for r in rows if name in r["metrics"]]
            if len(vals) < 2:
                continue
            q = statistics.quantiles(vals, n=4)
            med = statistics.median(vals)
            print(f"{name:28s} {path}: n={len(vals)} median {med:.6g} "
                  f"spread {(q[2] - q[0]) / med:.4%} min {min(vals):.6g} "
                  f"max {max(vals):.6g} correct "
                  f"{sum(r['correct'] for r in rows)}/{len(rows)}")
            if name == "setup_s" and len(vals) > 2:
                print(f"{'  setup_s without first run':28s} median "
                      f"{statistics.median(vals[1:]):.6g}")


if __name__ == "__main__":
    main(sys.argv[1:])
