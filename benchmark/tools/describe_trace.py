#!/usr/bin/env python3
"""Lists every plane and line of a kept profiler trace with its event
count and first event: the look by hand before trusting the reduction.

    python3 benchmark/run.py --workload <cell> --trace 1 --keep-trace ...
    python3 benchmark/tools/describe_trace.py .bench_out/trace-<cell>
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

if __name__ == "__main__":
    from nnsbench import traceread
    for row in traceread.describe(sys.argv[1]):
        print(*row, sep="\t")
