#!/usr/bin/env python3
"""What ``nnsbench/progtrace.py`` sees in a kept profiler trace: the
program's spans by name (count, mean, self time), the device programs by
name, the device-operation time by the scope's leading parts, and the
idle gaps by who they are charged to. The look by hand before trusting
a reader; ``tools/describe_trace.py`` lists the raw planes and lines.

    python3 benchmark/run.py --workload <cell> --trace 1 --keep-trace ...
    python3 benchmark/tools/describe_prog.py .bench_out/trace-<cell>
"""
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(trace_dir):
    from nnsbench import progtrace
    prog = progtrace.ProgTrace(progtrace.load(trace_dir))
    lo, hi = prog.window
    print(f"stretch\t{(hi - lo) / 1e9:.6f} s")
    by_name = {}
    for s in prog.spans:
        if prog.inside(s.hi, s.hi):
            by_name.setdefault((s.name, s.meta.get("element", "")),
                               []).append(s)
    print("span\telement\tkind\tn\tmean_ms\tself_mean_ms")
    for (name, element), spans in sorted(by_name.items()):
        mean = sum(s.hi - s.lo for s in spans) / len(spans) / 1e6
        kind = "wait" if spans[0].wait else "region"
        own = "" if spans[0].wait else "%.4f" % (
            sum(prog.self_ns(s) for s in spans) / len(spans) / 1e6)
        print(f"{name}\t{element}\t{kind}\t{len(spans)}\t{mean:.4f}\t{own}")
    modules = {}
    for name, a, b in prog.modules:
        if prog.inside(a, b):
            modules.setdefault(name, []).append(b - a)
    print("program\tn\tmean_ms\ttotal_s")
    for name, ds in sorted(modules.items(), key=lambda kv: -sum(kv[1])):
        print(f"{name}\t{len(ds)}\t{sum(ds) / len(ds) / 1e6:.4f}"
              f"\t{sum(ds) / 1e9:.6f}")
    scopes, total = {}, 0
    for _, a, b, scope in prog.ops:
        d = min(b, hi) - max(a, lo)
        if d <= 0:
            continue
        total += d
        # jit(name)/jit(main)/Module_3/block/attn/...: numbers folded,
        # the first five parts kept
        key = "/".join(re.sub(r"_\d+$", "", p)
                       for p in scope.split("/")[:5]) or "(no scope)"
        scopes[key] = scopes.get(key, 0) + d
    print("scope\ts\tshare_pct")
    for key, d in sorted(scopes.items(), key=lambda kv: -kv[1])[:25]:
        print(f"{key}\t{d / 1e9:.6f}\t{100.0 * d / total:.2f}")
    print("idle charged to\ts")
    for who, d in sorted(prog.idle_gaps().items(), key=lambda kv: -kv[1]):
        print(f"{who}\t{d / 1e9:.6f}")


if __name__ == "__main__":
    main(sys.argv[1])
