#!/usr/bin/env python3
"""obs-overhead gate: frame tracing must cost < 3% fps.

Runs a devres-shaped pipeline (device-resident tensortestsrc pool ->
jax filter -> delivery queue -> appsink) twice in SUBPROCESSES — once
with the observability plane enabled (NNS_TPU_OBS=1, the default) and
once hard-disabled (NNS_TPU_OBS=0, the control arm) — and fails when
the traced run's fps drops more than ``BUDGET_PCT`` below the control.
Subprocesses because the switch is read at import: the two arms must
never share an interpreter.

Reps INTERLEAVE the two arms (off, on, off, on, ...) so machine-load
drift lands on both equally, and each arm is represented by its BEST
rep (the gate compares ceilings — a GC pause in one rep must not fail
the build; the systematic cost we are bounding survives best-of, noise
does not).

The model is a zoo MLP sized so one buffer costs what a vision model's
per-buffer dispatch costs (~1-2 ms on the CPU mesh) — mobilenet_v2 at
batch 32 is minutes per child on CPU, far too slow for `make check`,
and a sub-100us toy model prices nothing but the GIL. Same shape,
CI-sized cadence.

Exit 0 = within budget; 1 = overhead above budget; 2 = harness failure.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import subprocess
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

BUDGET_PCT = 3.0
CAPS = ('"other/tensors,format=static,num_tensors=1,'
        'types=(string)float32,dimensions=(string)1024"')
# ~8.4M MACs/frame: ~1-2 ms on one CPU host thread (see module
# docstring)
MODEL = '"zoo://mlp?in_dim=1024&hidden=4096&out_dim=256&dtype=float32"'


def run_child(frames: int, warmup: int) -> None:
    """One measured run in THIS process; prints one JSON line."""
    import threading

    from nnstreamer_tpu.pipeline.parser import parse_launch

    desc = (f"tensortestsrc caps={CAPS} pattern=random device=true "
            f"unique=true num-buffers={warmup + frames} "
            "! queue max-size-buffers=8 "
            f"! tensor_filter framework=jax model={MODEL} "
            "prefetch-host=true ! queue max-size-buffers=32 "
            "! appsink name=out")
    pipe = parse_launch(desc)
    mark = {"n": 0, "t0": None, "t1": None}
    done = threading.Event()

    def on_buffer(buf):
        buf.host_arrays()  # materialize: deliver, don't just dispatch
        mark["n"] += 1
        if mark["n"] == warmup:
            mark["t0"] = time.perf_counter()
        elif mark["n"] == warmup + frames:
            mark["t1"] = time.perf_counter()
            done.set()

    pipe["out"].connect(on_buffer)
    pipe.start()
    ok = done.wait(timeout=300)
    pipe.stop()
    if not ok or mark["t0"] is None or mark["t1"] is None:
        print(json.dumps({"error": f"saw {mark['n']} buffers"}))
        sys.exit(2)
    print(json.dumps({"fps": frames / (mark["t1"] - mark["t0"])}))


def run_once(obs_on: bool, frames: int, warmup: int) -> float:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               NNS_TPU_OBS="1" if obs_on else "0",
               NNS_TPU_FLIGHT_DIR="")  # no abort dumps from the bench
    out = subprocess.run(
        [sys.executable, __file__, "--child",
         "--frames", str(frames), "--warmup", str(warmup)],
        env=env, capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        print(f"child (obs={'on' if obs_on else 'off'}) failed:\n"
              f"{out.stdout}\n{out.stderr}", file=sys.stderr)
        sys.exit(2)
    return json.loads(out.stdout.strip().splitlines()[-1])["fps"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--frames", type=int, default=600)
    ap.add_argument("--warmup", type=int, default=60)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--budget-pct", type=float, default=BUDGET_PCT)
    args = ap.parse_args(argv)
    if args.child:
        run_child(args.frames, args.warmup)
        return 0
    print("obs-overhead gate: devres row, tracing on vs off")
    samples = {False: [], True: []}
    for _ in range(args.reps):          # interleaved: drift hits both arms
        for obs_on in (False, True):
            samples[obs_on].append(
                run_once(obs_on, args.frames, args.warmup))
    for obs_on in (False, True):
        v = samples[obs_on]
        print(f"  obs={'on ' if obs_on else 'off'}: best {max(v):.1f} fps "
              f"(median {statistics.median(v):.1f}, {args.reps} reps)")
    off, on = max(samples[False]), max(samples[True])
    loss_pct = (off - on) / off * 100.0 if off else 0.0
    verdict = loss_pct <= args.budget_pct
    print(f"overhead: {loss_pct:+.2f}% (budget {args.budget_pct}%) -> "
          f"{'OK' if verdict else 'FAIL'}")
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
