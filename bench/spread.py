#!/usr/bin/env python3
"""Spreads of the runs ``bench/measure.sh`` wrote: for each metric and
set, the distance between the first and third quartiles
(``statistics.quantiles(n=4)``) as a share of the median.

    python bench/spread.py .bench_out/measure_<cell>.jsonl
"""

import json
import statistics
import sys
from collections import defaultdict


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(path):
    rows = [json.loads(line) for line in open(path)]
    by = defaultdict(lambda: defaultdict(list))
    correct = [(r["seed"], r["trace"], (r["result"] or {}).get("correct")) for r in rows]
    for r in rows:
        if r["trace"] == 0 and r["result"]:
            for k, m in r["result"]["metrics"].items():
                by[k][r["set"]].append(m["value"])
    for k, sets in by.items():
        for s, v in sorted(sets.items()):
            print(f"{k} set {s}: n={len(v)} median={statistics.median(v)} spread={spread(v) if len(v) > 1 else None}")
            print(f"    values {v}")
    print("correct", correct)


if __name__ == "__main__":
    main(sys.argv[1])
