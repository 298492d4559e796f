"""Share of the tiles the whole-router grouped kernel walks that it takes
at half their rows: the experts whose last tile holds at most half a
tile's rows, over the tiles walked, for each expert layer of each buffer
that arrived in the window (the program's third output tensor,
``expert_load``), both summed over all of them. Both counts are the
program's own (``nnstreamer_tpu.ops.grouped.half_tiles(counts, tile)``
and ``tiles_walked(counts, tile)`` at the ``tile`` its models pass,
``models.latent.EXPERT_TILE``), so the reader and the kernel's choice
cannot disagree. The seeded routing of the Trinity cell reads near 40.
It reads the routing and the tile, not the chip: the share of the walk
whose products ``nns_grouped_swiglu`` halves. None where the program has
no such count (a kernel that multiplies every tile whole), where the
loads are not of a chip that runs the kernel (the tile
loops of a chip that holds less than half its router,
``ops.grouped.takes_kernel``), and where nothing was routed.

Entry in BENCHMARK.json: unit %, better higher, source program_counter,
layer "kernels", moves ``frames_per_s``."""
import numpy as np


def read(run):
    loads = run["results"].get("expert_loads")
    if not loads:
        return None
    try:
        from nnstreamer_tpu.models.latent import EXPERT_TILE
        from nnstreamer_tpu.ops.grouped import (half_tiles, takes_kernel,
                                                tiles_walked)
    except ImportError:
        return None
    counts = np.stack(loads).astype(np.int64)           # [buffers, layers, held]
    sizes = run["sizes"]
    held = sizes.get("num_experts")
    router = sizes.get("num_experts_total", held)
    if counts.shape[-1] != held or not takes_kernel(held, router) \
            or not counts.any():
        return None
    walked = int(np.sum(tiles_walked(counts, EXPERT_TILE)))
    return float(100.0 * np.sum(half_tiles(counts, EXPERT_TILE)) / walked)
