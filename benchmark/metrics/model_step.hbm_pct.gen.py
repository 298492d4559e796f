"""Share of the chip's memory bandwidth that the window's decode steps
needed at the least: every step reads the weights once, and the live keys
and values of the streams it serves (from counts: ``decode_steps`` and
the tokens each request held), over the window's seconds x peak bytes/s.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``tokens_per_s``."""
from nnsbench import costs


def read(run):
    a = run["counters"].get("llm_start")
    b = run["counters"].get("llm_end")
    if run["peaks"] is None or not a or not b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    # each served token was one lane of one step, reading its context
    kv_tokens = sum(served * (plen + served / 2.0)
                    for plen, served in run["results"]["work"])
    need = steps * costs.gpt_decode_step_bytes(run["sizes"], 0) \
        + kv_tokens * costs.gpt_kv_bytes_per_token(run["sizes"])
    return 100.0 * need / (run["window_s"]
                           * run["peaks"]["hbm_bytes_per_s"])
