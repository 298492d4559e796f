#!/usr/bin/env python3
"""benchmark/run.py - one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: refuses any platform but ``tpu`` (exit 2, no result line),
names the device, builds the cell from the files its names point to
(``configs/<config>.json`` + ``.py``, ``traffic/<mix>.json`` and the
``drivers/<kind>.py`` it names, ``workloads/<cell>.json``,
``checks/<family>.py``, ``metrics/<metric>.py``), warms up the cell's
own shapes (set-up), measures one window, frees the program's state,
compares what the window produced with the plain reference, and prints
the contract's one JSON object as the last line of standard output.
``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the per-layer
metrics and the device's breakdown from a profiler trace of part of the
window.

``--rehearsal`` is for a sandbox without a chip: the configuration's tiny
``rehearsal`` sizes, CPU allowed, every line prefixed ``REHEARSAL`` so
that no line of it can be taken for a measurement. ``--selftest`` checks
the yardstick's own arithmetic (seconds, CPU).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import faulthandler      # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

OUT_DIR = os.path.join(ROOT, ".bench_out")     # traces; git-ignored
CONTROLS = ("fp8", "fp8_e5m2")   # the precisions below bfloat16
DEADLINE_S = 340          # a hung run dumps its stacks and exits non-zero
DEADLINE_COLD_S = 1150    # ... or this, when the compile cache is empty


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_part(folder, name, attr):
    """What belongs to one driver kind, one family's check or one
    per-layer metric sits in a file of its own, found by name:
    ``<folder>/<name>.py``, of which ``attr`` is taken."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        folder + "_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def load_reader(name):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, or,
    for one quantity split by the end-to-end metric it moves
    (``device.idle_pct.vision``), the quantity's ``metrics/<quantity>.py``."""
    if not os.path.isfile(os.path.join(HERE, "metrics", name + ".py")):
        name = name.rsplit(".", 1)[0]
    return load_part("metrics", name, "read")


class Ctx:
    """What a driver and the checks need to know of this run."""

    def __init__(self, cell, config, traffic, workload, seed, rehearsal):
        self.cell, self.config, self.workload = cell, config, workload
        self.seed, self.rehearsal = int(seed), rehearsal
        self.sizes = {k: v for k, v in config.items()
                      if isinstance(v, (int, float)) and not
                      isinstance(v, bool)}
        self.traffic = dict(traffic)
        if rehearsal:
            self.sizes.update(config["rehearsal"])
            self.traffic.update(traffic.get("rehearsal", {}))
        self.model_file = os.path.join(HERE, "configs", cell["config"] + ".py")
        self.compile_wait_s = 1000.0
        self.session = None


class Tracer:
    """Takes a ``jax.profiler`` trace of ``seconds`` of the window, from
    its own thread, and marks the traced stretch with a span."""

    def __init__(self, out_dir, delay_s, seconds):
        self.out_dir, self.delay_s, self.seconds = out_dir, delay_s, seconds
        self.thread = None
        self.error = None
        self.stop_s = None        # what stopping the profiler took

    def run_inside(self, t0, end):
        self.thread = threading.Thread(target=self._body, args=(t0, end),
                                       daemon=True, name="bench-tracer")
        self.thread.start()

    def _body(self, t0, end):
        import jax
        try:
            time.sleep(max(0.0, t0 + self.delay_s - time.perf_counter()))
            span = min(self.seconds, max(0.5, end - time.perf_counter() - 0.5))
            # no Python call tracing: it slows the element threads and
            # swells the trace; the harness's spans are TraceMe events
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench.trace_window"):
                    time.sleep(span)
            finally:
                t = time.perf_counter()
                jax.profiler.stop_trace()
                self.stop_s = time.perf_counter() - t
        except Exception as exc:  # noqa: BLE001 - thread boundary: reported
            self.error = exc

    def finish(self):
        if self.thread is not None:
            self.thread.join(240.0)
        if self.error is not None:
            raise self.error


def device_block(devices):
    dev = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(name, seed, seconds, trace, rehearsal=False, say=None,
             fault=None, control=False, keep_trace=False, dump=""):
    """Drives one run and returns the result object (not printed).
    ``control`` also reads the checks' numbers for the lower-precision
    control (tools/calibrate.py and the tests; never a benchmark run)."""
    from nnsbench import compare, compilewatch, generator, peaks, session, stats
    say = say or (lambda line: None)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {c["name"]: c for c in bench["workloads"]}
    workload = load_json("workloads", name + ".json")
    if name in cells:
        cell = cells[name]
    elif rehearsal:
        # a new cell's files can be rehearsed before BENCHMARK.json lists it
        cell = workload
    else:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; known: "
                         f"{sorted(cells)}")
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    ctx = Ctx(cell, config, traffic, workload, seed, rehearsal)

    t_jax = time.perf_counter()
    import jax
    devices = jax.devices()
    reach_s = time.perf_counter() - t_jax
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)}")
    if not rehearsal:
        if dev.platform != "tpu" or len(devices) < cell["chips"]:
            print(f"benchmark: found {len(devices)} {dev.platform!r} "
                  f"device(s); cell {name} needs {cell['chips']} 'tpu' - "
                  "refusing to run (use --rehearsal in a sandbox)",
                  file=sys.stderr)
            raise SystemExit(2)
        chip = peaks.peaks_of(dev.device_kind)
    else:
        chip = None
    devices = devices[:cell["chips"]]
    watch = compilewatch.CompileWatch().install()

    ctx.session = session.activate(session.Session(
        config, ctx.sizes, ctx.seed, ctx.traffic))
    ctx.session.fault = fault
    driver = load_part("drivers", traffic["kind"], "Driver")(ctx)
    tracer = None
    trace_dir = os.path.join(OUT_DIR, "trace-" + name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir, 1.0, float(traffic.get(
            "trace_seconds", 4.0)))
    try:
        driver.setup()
        setup = watch.snapshot()
        window = generator.Window(seconds, tracer)
        setup_s = time.perf_counter() - T_PROCESS
        driver.run(window)
        in_window = watch.snapshot()["backend_compiles"] \
            - setup["backend_compiles"]
        if tracer is not None:
            tracer.finish()
        device = device_block(devices)
        results = driver.results()
    finally:
        driver.teardown()
        session.deactivate()
    window_s = window.t1 - window.t0
    # the reference runs only now: the window is closed, the peak is read
    # and the program's pipelines are gone
    t_check = time.perf_counter()
    check = load_part("checks", config["family"], "check")
    # the limits are set from readings at the cell's own size; the tiny
    # rehearsal sizes read otherwise and have limits of their own
    limits = workload["rehearsal_limits"] if rehearsal else workload["limits"]
    numbers, compared = check(driver, results, ctx, limits)
    check_s = time.perf_counter() - t_check
    controls = {kind: check(driver, results, ctx, limits, control=kind)
                for kind in CONTROLS} if control else None

    failed = results["failed"] + in_window
    run = {"ctx": ctx, "sizes": ctx.sizes, "config": config,
           "traffic": ctx.traffic, "results": results, "window_s": window_s,
           "counters": driver.counters, "samples": window.samples,
           "peaks": chip, "trace": None, "device": device}
    quantities = {
        "units_per_s": stats.rate(results["units_delivered"], window_s),
        "latency_p95_ms": stats.percentile(results.get("latencies_ms"), 95),
        "first_latency_p95_ms": stats.percentile(
            results.get("first_latencies_ms"), 95),
        "setup_s": setup_s,
        **results.get("quantities", {}),
    }
    out_metrics = {}
    breakdown = trace_cost = None
    if not trace:
        for m in bench["end_to_end"]:
            if name not in m.get("workloads", [name]):
                continue
            q = "setup_s" if m["name"] == "setup_s" \
                else traffic["end_to_end"].get(m["name"])
            value = quantities.get(q)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from nnsbench import traceread
        t_read = time.perf_counter()
        try:
            run["trace"] = traceread.reduce(traceread.load(trace_dir))
        except (FileNotFoundError, ValueError) as exc:
            if not rehearsal:
                raise
            say(f"trace: not reduced ({exc})")
        if run["trace"] is not None:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            breakdown = {"device_ops": run["trace"]["device_ops"],
                         "idle_gaps": run["trace"]["idle_gaps"]}
        for m in bench["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            value = load_reader(m["name"])(run)
            if value is not None:
                out_metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        trace_cost = {"stop_s": tracer.stop_s,
                      "read_s": time.perf_counter() - t_read}
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)

    comp = watch.snapshot()
    result = {
        "correct": compare.verdict(numbers),
        "attempted": results["attempted"], "failed": failed,
        "metrics": out_metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    series = {k: results[k] for k in ("latencies_ms", "first_latencies_ms",
                                      "pushed_at_s") if k in results}
    result["info"] = {
        "workload": name, "seed": ctx.seed, "window_s": window_s,
        "samples": {k: len(v) for k, v in series.items()},
        "compilations_in_window": in_window, "check_s": check_s,
        "trace_cost": trace_cost,
        "compared": compared,
        "setup_split_s": {
            "reach_the_chip": reach_s,
            **{k: v for k, v in setup["seconds"].items()},
            "cache_hits": setup["cache_hits"],
            "cache_misses": setup["cache_misses"]},
        "compile_after_setup": {
            "cache_hits": comp["cache_hits"] - setup["cache_hits"],
            "cache_misses": comp["cache_misses"] - setup["cache_misses"]},
        "counters": driver.counters}
    if controls is not None:
        result["info"]["control"] = {
            kind: c[1]["read"] for kind, c in controls.items()}
        result["info"]["control_correct"] = {
            kind: compare.verdict(c[0]) for kind, c in controls.items()}
    if dump:
        os.makedirs(os.path.dirname(dump) or ".", exist_ok=True)
        with open(dump, "w") as f:
            json.dump(series, f)
    result["checks"] = numbers         # last: each number beside its limit
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--dump", default="",
                    help="also write every request's latency to this file")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under .bench_out/")
    args = ap.parse_args(argv)
    if args.selftest:
        from selftest import selftest
        return selftest.main()
    if not args.workload:
        ap.error("--workload is required")
    cold = not os.path.isdir(os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache")))
    faulthandler.dump_traceback_later(
        DEADLINE_COLD_S if cold else DEADLINE_S, exit=True)
    prefix = "REHEARSAL " if args.rehearsal else ""

    def say(line):
        print(prefix + line, file=sys.stderr, flush=True)

    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      rehearsal=args.rehearsal, say=say,
                      keep_trace=args.keep_trace, dump=args.dump)
    for key, n in result["checks"].items():
        say(f"check {key}: value={n['value']:.6g} limit={n['limit']:.6g}")
    say(f"correct={result['correct']}")
    print(prefix + json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as exc:
        code = exc.code if isinstance(exc.code, int) else 1
        if not isinstance(exc.code, int) and exc.code:
            print(exc.code, file=sys.stderr)
    except BaseException:   # noqa: BLE001 - report, then leave non-zero
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of stopped pipelines must not keep the process, and
    # with it the chip, alive
    os._exit(code)
