"""Whole-step share of the chip's peak for a streamed window / full
grouped-query expert model: sequences delivered in the window x the
useful FLOPs of one sequence's scoring pass
(``nnsbench/costs_afmoe.py``: the pairs a window keeps on a sliding
layer, the causal pairs on a full one, the router, the shared expert and
the chosen experts held here, the head over the slice) over the window's
seconds x the peak bf16 FLOP/s. It counts the same work whatever
implements it.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``frames_per_s``."""
from nnsbench import costs_afmoe


def read(run):
    if run["peaks"] is None or "tokens_per_buffer" not in run["traffic"]:
        return None
    sizes = {**run["config"], **run["sizes"]}     # layer_types is a list
    flops = run["results"]["units_delivered"] * costs_afmoe.sequence_flops(
        sizes, int(run["traffic"]["tokens_per_buffer"]))
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
