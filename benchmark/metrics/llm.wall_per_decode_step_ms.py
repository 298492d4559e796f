"""The window's seconds over the decode steps it ran (``decode_steps``,
end of window less start): the whole window over all its steps, prefill
and host time included.

Entry in BENCHMARK.json: unit ms, better lower, source
program_counter, layer "filter backend llm", moves ``tokens_per_s``."""


def read(run):
    a = run["counters"].get("llm_start")
    b = run["counters"].get("llm_end")
    if not a or not b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    return 1e3 * run["window_s"] / steps if steps > 0 else None
