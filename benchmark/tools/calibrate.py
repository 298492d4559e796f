#!/usr/bin/env python3
"""Reads, in one process, the numbers a cell's ``correct`` compares: for
each seed one short window of the cell at its own size and load, the
program's number against the plain reference and the control's (the
reference in fp8) beside it. The limits in ``workloads/<cell>.json`` are
set from these readings (PERF.md section 2).

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds 6 [--control-seeds 3] [--out chiprun_out/calib.jsonl]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main():
    import run as bench_run
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        res = bench_run.run_cell(
            args.workload, seed, args.seconds, 0, rehearsal=args.rehearsal,
            control=i < args.control_seeds,
            say=lambda line: print(line, file=sys.stderr, flush=True))
        row = {"workload": args.workload, "seed": seed,
               "correct": res["correct"], "attempted": res["attempted"],
               "failed": res["failed"],
               "program": res["info"]["compared"]["read"],
               "control": (res["info"].get("control") or {}),
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "compared": res["info"]["compared"],
               "check_s": res["info"]["check_s"],
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        line = json.dumps(row, default=float)
        print(("REHEARSAL " if args.rehearsal else "") + line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except BaseException:   # noqa: BLE001 - report, then leave non-zero
        import traceback
        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
