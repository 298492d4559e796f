#!/usr/bin/env python3
"""How much of each number a ``glm_dsa`` cell compares do the discrete
choices alone move? For each seed: the cell's weights and check
sequences (no pipeline, no window), the plain reference in float32
against itself with only the index scores and the router's biased scores
rounded to bfloat16 before the top-k choices are made. A diagnostic
(PERF.md section 2); it decides nothing.

    python3 benchmark/tools/select_diag.py --workload <cell> --seeds 11,12 \\
        [--rehearsal] [--out chiprun_out/select_diag.jsonl]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main():
    import run as bench_run
    from nnsbench import session
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    workload = bench_run.load_json("workloads", args.workload + ".json")
    config = bench_run.load_json("configs", workload["config"] + ".json")
    traffic = bench_run.load_json("traffic", workload["traffic"] + ".json")
    check = bench_run.load_part("checks", config["family"], "check")
    limits = workload["rehearsal_limits" if args.rehearsal else "limits"]
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = bench_run.Ctx(workload, config, traffic, workload, seed,
                            args.rehearsal)
        ctx.session = session.activate(session.Session(
            config, ctx.sizes, ctx.seed, ctx.traffic))
        try:
            scope = {}
            with open(ctx.model_file) as f:
                exec(compile(f.read(), ctx.model_file, "exec"), scope)
            scope["get_model"]()          # makes the seed's weights
            driver = bench_run.load_part(
                "drivers", traffic["kind"], "Driver")(ctx)
            _, compared = check(driver, {"answers": []}, ctx, limits,
                                control="bf16_select")
        finally:
            session.deactivate()
        line = json.dumps({"workload": args.workload, "seed": seed,
                           "bf16_select": compared["read"]}, default=float)
        print(("REHEARSAL " if args.rehearsal else "") + line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
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
