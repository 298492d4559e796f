"""95th percentile over all gaps between one stream's consecutive tokens
at the sink, milliseconds. Tokens leave the filter a chunk at a time, so
most gaps are near zero and one in ``chunk`` is a whole dispatch.

Entry in BENCHMARK.json: unit ms, better lower, source
host_clock, layer "filter backend llm", moves ``tokens_per_s``."""
from nnsbench import stats


def read(run):
    return stats.percentile(run["results"].get("token_gaps_ms"), 95)
