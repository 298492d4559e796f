"""Imbalance of the routed experts held here: the busiest held expert's
token-expert pairs over the mean of the held experts', for each expert
layer of each buffer that arrived in the window (the program's third
output tensor, ``expert_load``), averaged. 1 is even; the busiest
expert sets how many tiles the grouped product runs.

Entry in BENCHMARK.json: unit x, better lower, source program_counter,
layer "model step", moves ``frames_per_s``."""
import numpy as np


def read(run):
    loads = run["results"].get("expert_loads")
    if not loads:
        return None
    per_layer = np.stack(loads).astype(np.float64)      # [buffers, layers, held]
    mean = per_layer.mean(-1)
    if not (mean > 0).all():
        return None
    return float((per_layer.max(-1) / mean).mean())
