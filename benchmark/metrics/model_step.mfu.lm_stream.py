"""Whole-step share of the chip's peak for a streamed language model:
sequences delivered in the window x the useful FLOPs of one sequence's
scoring pass (``nnsbench/costs_glm.py``: selected attention pairs,
causal indexer pairs, the expected share of routed experts held here)
over the window's seconds x the peak bf16 FLOP/s. It counts the same
work whatever implements it, so a masked-dense attention reads low.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``frames_per_s``."""
from nnsbench import costs_glm


def read(run):
    if run["peaks"] is None or "tokens_per_buffer" not in run["traffic"]:
        return None
    flops = run["results"]["units_delivered"] * costs_glm.sequence_flops(
        run["sizes"], int(run["traffic"]["tokens_per_buffer"]))
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
