#!/usr/bin/env python3
"""Spreads of a cell's runs by the rule its bounds are set by: for each
metric the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, per
set and the wider of the two, and the bound that is five times that.

    python3 benchmark/tools/spread.py chiprun_out/gen_set1_*.out -- chiprun_out/gen_set2_*.out

Each file holds one run's standard output (the result is its last line);
``--`` separates the sets."""
from __future__ import annotations

import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def last_result(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def main(argv):
    from nnsbench import stats
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    per_set = []
    for paths in sets:
        runs = [last_result(p) for p in paths]
        bad = [p for p, r in zip(paths, runs)
               if not r["correct"] or r["failed"]]
        if bad:
            print("not correct or with failures:", bad)
        names = sorted({k for r in runs for k in r["metrics"]})
        per_set.append({k: [r["metrics"][k]["value"] for r in runs
                            if k in r["metrics"]] for k in names})
    for k in per_set[0]:
        row = []
        for i, s in enumerate(per_set):
            v = s.get(k, [])
            row.append(f"set{i + 1} n={len(v)} median="
                       f"{statistics.median(v):.6g} spread="
                       f"{stats.spread(v):.4%}")
        widest = max(stats.spread(s[k]) for s in per_set if k in s)
        print(f"{k}: " + "; ".join(row)
              + f"; widest={widest:.4%}; x5={5 * widest:.4%}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
