"""Share of the pairs the sliding-window attention kernel multiplies
that its window keeps, for one sequence: ``sum_t min(t + 1,
sliding_window_size)`` over the query-key pairs of every key tile the
window walk of ``nns_masked_attention`` visits, a head. The walk is the
program's own count (``nnstreamer_tpu.ops.sparse_attention.pairs_walked``
at the ``block_q`` its models pass, ``models.latent.BLOCK_Q``): a query
tile visits each key tile from its window's first to its diagonal,
whole. A window of one tile plus one key (513 over tiles of 512) keeps
about half the pairs of the two tiles it reaches. It reads the window
and the tiles, not the chip: what the kernel's share of its roofline
cannot pass on a sliding layer while it multiplies whole tiles. None
where the configuration has no ``sliding_window_size`` or the program
no such count.

Entry in BENCHMARK.json: unit %, better higher, source program_counter,
layer "kernels", moves ``frames_per_s``."""
from nnsbench.costs_glm import selected_pairs


def read(run):
    sizes = {**run["config"], **run["sizes"]}
    window = sizes.get("sliding_window_size")
    if window is None or "tokens_per_buffer" not in run["traffic"]:
        return None
    try:
        from nnstreamer_tpu.models.latent import BLOCK_Q
        from nnstreamer_tpu.ops.sparse_attention import pairs_walked
    except ImportError:
        return None
    s = int(run["traffic"]["tokens_per_buffer"])
    window = int(window)
    return 100.0 * selected_pairs(s, window) / pairs_walked(
        s, block_q=BLOCK_Q, window=window)
