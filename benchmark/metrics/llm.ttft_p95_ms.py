"""95th percentile over all requests pushed in the window of: prompt
pushed -> its first token at the sink, milliseconds (host clock). A
per-layer metric for now: across runs it spreads by 11-17 %, too wide
for a bound (PERF.md section 2).

Entry in BENCHMARK.json: unit ms, better lower, source
host_clock, layer "filter backend llm", moves ``tokens_per_s``."""
from nnsbench import stats


def read(run):
    return stats.percentile(run["results"].get("first_latencies_ms"), 95)
