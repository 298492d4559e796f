"""95th percentile of a request's wait in the batcher, milliseconds
(``scheduler.report()`` ``queue_delay_us``, the all-run reservoir; the
ramp before the window is in it).

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "serve", moves ``latency_p95_ms``."""


def read(run):
    rep = run["counters"].get("scheduler") or {}
    p95 = (rep.get("queue_delay_us") or {}).get("p95")
    return None if p95 is None else p95 / 1e3
