"""Whole-step share of the chip's peak: frames delivered in the window x
the FLOPs one frame's forward needs (padding rows not counted) over the
window's seconds x the peak bf16 FLOP/s.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``frames_per_s``."""
from nnsbench import costs


def read(run):
    if run["peaks"] is None:
        return None
    flops = run["results"]["units_delivered"] * costs.vit_flops_per_frame(
        run["sizes"])
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
