"""Whole-step share of the chip's peak: (prompt tokens prefilled +
tokens decoded in the window) x the FLOPs each needs at its context, over
the window's seconds x the peak bf16 FLOP/s. Padding of prefill buckets
and idle lanes is not counted.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``tokens_per_s``."""
from nnsbench import costs


def read(run):
    if run["peaks"] is None:
        return None
    flops = 0.0
    for plen, served in run["results"]["work"]:
        flops += plen * costs.gpt_flops_per_token(
            run["sizes"], (plen + 1) / 2.0, with_head=False)
        flops += served * costs.gpt_flops_per_token(
            run["sizes"], plen + served / 2.0)
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
