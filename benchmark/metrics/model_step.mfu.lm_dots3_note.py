"""Whole-step share of the chip's peak for a streamed model of full and
sliding-window latent attention: sequences delivered in the window x
the useful FLOPs of one sequence's scoring pass
(``nnsbench/costs_dots3_note.py``: the pairs the indexer selects and
every causal indexer pair on a full layer, the pairs the window keeps
on a sliding one, each kind at its own sizes, the router, the shared
expert and the chosen experts held here, the head over the slice) over
the window's seconds x the peak bf16 FLOP/s. It counts the same work
whatever implements it.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``frames_per_s``."""
from nnsbench import costs_dots3_note


def read(run):
    if run["peaks"] is None or "tokens_per_buffer" not in run["traffic"]:
        return None
    sizes = {**run["config"], **run["sizes"]}     # layer_types is a list
    flops = run["results"]["units_delivered"] \
        * costs_dots3_note.sequence_flops(
            sizes, int(run["traffic"]["tokens_per_buffer"]))
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
