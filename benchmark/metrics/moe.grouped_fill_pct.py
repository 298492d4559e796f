"""Share of the rows the whole-router grouped product multiplies that
somebody reads: the token-expert pairs served over the rows of the
tiles the kernel ``nns_grouped_swiglu`` walks for them, for each expert
layer of each buffer that arrived in the window (the program's third
output tensor, ``expert_load``), pairs and tiles summed over all of
them. The tiles are the program's own count
(``nnstreamer_tpu.ops.grouped.tiles_walked(counts, tile)`` at the
``tile`` its models pass, ``models.latent.EXPERT_TILE``): every expert
starts on a tile of its own, so a routing costs ``sum(ceil(count /
tile))`` tiles whatever the order of the pairs. 100 is a routing whose
every expert serves whole tiles; the seeded one, one expert near 1,700
pairs and a third under 64, reads near 60. It reads the routing and the
tile, not the chip: what ``kernel.nns_grouped_swiglu.roofline_pct``
cannot pass while the kernel multiplies whole tiles. None where the
program has no such function (every parent of PR 37: its walk shares
tiles between experts and has no count of its own) and where the loads
are not the whole router's (the tile loops of a chip that holds a share
of the experts).

Entry in BENCHMARK.json: unit %, better higher, source program_counter,
layer "kernels", moves ``frames_per_s``."""
import numpy as np


def read(run):
    loads = run["results"].get("expert_loads")
    if not loads:
        return None
    try:
        from nnstreamer_tpu.models.latent import EXPERT_TILE
        from nnstreamer_tpu.ops.grouped import tiles_walked
    except ImportError:
        return None
    counts = np.stack(loads).astype(np.int64)           # [buffers, layers, held]
    if counts.shape[-1] != run["sizes"].get("num_experts") or not counts.any():
        return None
    rows = EXPERT_TILE * int(np.sum(tiles_walked(counts, EXPERT_TILE)))
    return float(100.0 * counts.sum() / rows)
