"""Whole-step share of the chip's peak for a streamed shortcut-connected
expert model: sequences delivered in the window x the useful FLOPs of
one sequence's scoring pass (``nnsbench/costs_longcat.py``: causal
attention pairs, two attentions and two dense MLPs a layer, the expected
share of routed experts held here, identity experts nothing) over the
window's seconds x the peak bf16 FLOP/s. It counts the same work
whatever implements it.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``frames_per_s``."""
from nnsbench import costs_longcat


def read(run):
    if run["peaks"] is None or "tokens_per_buffer" not in run["traffic"]:
        return None
    flops = run["results"]["units_delivered"] * costs_longcat.sequence_flops(
        run["sizes"], int(run["traffic"]["tokens_per_buffer"]))
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
