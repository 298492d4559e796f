"""Dispatch-to-completion time of one invoke, milliseconds: the
filter's rolling ``latency_average_us`` (last 10 invokes), sampled every
quarter second through the window and averaged.

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "tensor_filter + in-flight window", moves ``latency_p95_ms``."""


def read(run):
    xs = run["samples"].get("filter_latency_us") or []
    if not xs:
        return None
    return sum(xs) / len(xs) / 1e3
