"""Share of the rows the split form of the grouped product multiplies
that somebody reads: the token-expert pairs served over the rows of the
tiles that ``nns_grouped_gate_up`` and ``nns_grouped_down`` multiply for
them, for each expert layer of each buffer that arrived in the window
(the program's third output tensor, ``expert_load``), pairs and rows
summed over all of them. The rows are the program's own count at the
``tile`` its models pass (``models.latent.EXPERT_TILE``): every expert
starts on a tile of its own (``nnstreamer_tpu.ops.grouped.tiles_walked``),
and an expert's last tile of at most half its rows is multiplied at
half (``half_tiles``). It reads the routing and the tile, not the chip:
how much of the split form's walk is useful, and, by reading at all,
which cells take the form. None where the program has no split form
(a program that ran the tile loops there instead), where the loads are
of a chip that does not take it (``ops.grouped.takes_split``: the
whole-router kernel's share), and where nothing was routed.

A layer whose held experts serve more pairs than one pass of the split
form holds (``ops.grouped._pass_pairs``: twice the pairs an even routing
serves here, 16,384 of dots3's 65,536) takes a second pass over the
rows past them, and an expert cut between two passes starts a tile in
each; this reader counts the walk of one pass. On the seeded routing of
the dots3 cell (``moe.load_max_over_mean`` near 1.26, about 8,192 pairs
served a layer) no layer comes near a second pass, so no metric reads
it.

Entry in BENCHMARK.json: unit %, better higher, source program_counter,
layer "kernels", moves ``frames_per_s``."""
import numpy as np


def read(run):
    loads = run["results"].get("expert_loads")
    if not loads:
        return None
    try:
        from nnstreamer_tpu.models.latent import EXPERT_TILE
        from nnstreamer_tpu.ops.grouped import (half_tiles, takes_split,
                                                tiles_walked)
    except ImportError:
        return None
    counts = np.stack(loads).astype(np.int64)   # [buffers, layers, held]
    sizes = run["sizes"]
    # the names of the configurations' own files: ``num_experts`` (of
    # ``num_experts_total``) or ``n_routed_experts`` (of
    # ``n_routed_experts_total``, beside ``zero_expert_num`` identity
    # experts, whose pairs the load's last column counts)
    held = sizes.get("num_experts", sizes.get("n_routed_experts"))
    router = sizes.get("num_experts_total", sizes.get(
        "n_routed_experts_total", held))
    if held is not None and sizes.get("zero_expert_num"):
        router += sizes["zero_expert_num"]
        counts = counts[..., :held]
    if counts.shape[-1] != held or not takes_split(held, router) \
            or not counts.any():
        return None
    half = EXPERT_TILE // 2
    rows = EXPERT_TILE * int(np.sum(tiles_walked(counts, EXPERT_TILE))) \
        - (EXPERT_TILE - half) * int(np.sum(half_tiles(counts, EXPERT_TILE)))
    return float(100.0 * counts.sum() / rows)
