"""Share of the token-expert pairs that identity (zero-computation)
experts took: the last column of the program's third output tensor,
``expert_load`` ``[layers, held + 1]``, over tokens x ``moe_topk`` x
layers, every buffer that arrived in the window, averaged. It reads the
weights and the router, not the program's speed: the pairs the
mechanism makes free (a third when the choice is even over a router of
which a third are identities). None for a configuration without
identity experts.

Entry in BENCHMARK.json: unit %, better higher, source program_counter,
layer "model step", moves ``frames_per_s``."""
import numpy as np


def read(run):
    loads = run["results"].get("expert_loads")
    sizes = run["sizes"]
    if not loads or not sizes.get("zero_expert_num"):
        return None
    zero = np.stack(loads)[:, :, -1].astype(np.float64)   # [buffers, layers]
    pairs = int(run["traffic"]["tokens_per_buffer"]) * sizes["moe_topk"]
    return float(100.0 * zero.mean() / pairs)
