"""Whole-step share of the chip's peak for a streamed hybrid of gated
delta-rule (KDA) and latent-attention layers with a sparse expert layer:
sequences delivered in the window x the useful FLOPs of one sequence's
scoring pass (``nnsbench/costs_kimi_linear.py``: a KDA layer's
projections and its recurrence as the token-by-token form states it,
the MLA layer's causal pairs, the router, the shared expert and the
chosen experts held here, the head over the slice) over the window's
seconds x the peak bf16 FLOP/s. It counts the same work whatever
implements it.

Entry in BENCHMARK.json: unit %, better higher, source
program_counter, layer "model step", moves ``frames_per_s``."""
from nnsbench import costs_kimi_linear


def read(run):
    if run["peaks"] is None or "tokens_per_buffer" not in run["traffic"]:
        return None
    sizes = {**run["config"], **run["sizes"]}     # the nested group too
    flops = run["results"]["units_delivered"] \
        * costs_kimi_linear.sequence_flops(
            sizes, int(run["traffic"]["tokens_per_buffer"]))
    return 100.0 * flops / (run["window_s"] * run["peaks"]["flops_bf16"])
