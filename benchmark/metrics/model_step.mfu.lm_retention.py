"""Whole-step share of the chip's peak for a streamed decoder of gated
power-retention layers: buffers delivered in the window x the useful
FLOPs of one buffer's scoring pass (``nnsbench/costs_brumby.py``: every
layer's projections and MLP, its retention as the token-by-token form
states it at ``phi``'s own rows, the head over the slice) over the
window's seconds x the peak bf16 FLOP/s. It counts the same work
whatever implements it, and the same for every buffer of a document.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``frames_per_s``."""
from nnsbench import costs_brumby


def read(run):
    if run["peaks"] is None or "tokens_per_buffer" not in run["traffic"]:
        return None
    flops = run["results"]["units_delivered"] * costs_brumby.buffer_flops(
        run["sizes"], int(run["traffic"]["tokens_per_buffer"]))
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
