"""``moe.half_tile_pct`` on loads made by hand (CPU; ``python -m pytest
benchmark/tests -q``):

* the experts whose last tile holds at most half a tile's rows over the
  tiles walked, all layers of all arrived buffers together, at the
  program's own tile;
* the share is ``ops.grouped.half_tiles`` over ``tiles_walked``, the
  counts the kernel itself takes its half steps by;
* no loads, loads of nothing, loads of a chip that takes the tile loops
  (a sixteenth of its router), a program without the count: None, no
  raise;
* the entry stands at the end of ``BENCHMARK.json``'s ``per_layer`` and
  lists the Trinity and Kimi-Linear cells.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

METRIC = "moe.half_tile_pct"
CELLS = ["trinity_mini_pp8_l5.lmstream_s4096",
         "kimi_linear_ep2_l5.lmstream_s8192"]


def _run(loads, held=4, router=None):
    sizes = {"num_experts": held}
    if router is not None:
        sizes["num_experts_total"] = router
    return {"results": {"expert_loads": loads}, "sizes": sizes}


@pytest.fixture
def tile(monkeypatch):
    """The program's tile cut to 8 rows, so that loads written by hand
    cross its half."""
    from nnstreamer_tpu.models import latent
    monkeypatch.setattr(latent, "EXPERT_TILE", 8)
    return 8


@pytest.mark.parametrize("loads,half,walked", [
    # last tiles of 4 (half), 5 (one row over), 1 and a whole tile
    ([[[12, 5, 1, 8]]], 2, 5),
    # every expert's rows in one half tile, one nobody chose
    ([[[4, 3, 0, 2]]], 3, 3),
    # nothing at half: whole tiles and last tiles over half
    ([[[16, 7, 6, 24]]], 0, 7),
    # two buffers of two layers, summed and not averaged
    ([[[9, 0, 0, 0], [4, 4, 4, 4]], [[13, 3, 0, 0], [0, 0, 0, 20]]], 7, 12),
], ids=["mixed", "half_tiles_only", "none_half", "buffers_and_layers"])
def test_half_tiles_over_tiles_walked(loads, half, walked, tile):
    read = bench_run.load_reader(METRIC)
    assert read(_run([np.asarray(b) for b in loads])) == pytest.approx(
        100.0 * half / walked)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_share_is_the_kernels_own_count(seed):
    """At the program's tile (256 rows), on a skewed routing of 128
    experts: the reader's share is the program's ``half_tiles`` over its
    ``tiles_walked``, and ``half_tiles`` is the number of experts whose
    ``count % tile`` lies in ``(0, tile // 2]``."""
    from nnstreamer_tpu.models.latent import EXPERT_TILE
    from nnstreamer_tpu.ops.grouped import half_tiles, tiles_walked
    rng = np.random.default_rng(seed)
    counts = rng.multinomial(4096 * 8, rng.dirichlet(np.ones(128)),
                             size=(2, 4))
    rest = counts % EXPERT_TILE
    by_hand = int(((rest > 0) & (rest <= EXPERT_TILE // 2)).sum())
    assert int(np.sum(half_tiles(counts, EXPERT_TILE))) == by_hand > 0
    read = bench_run.load_reader(METRIC)
    assert read(_run(list(counts), held=128)) == pytest.approx(
        100.0 * by_hand / int(np.sum(tiles_walked(counts, EXPERT_TILE))))


def test_nothing_to_read_is_none(monkeypatch):
    read = bench_run.load_reader(METRIC)
    loads = [np.asarray([[8, 9, 0, 7]])]
    assert read(_run(loads)) is not None
    # half a router held: the kernel's path, read
    assert read(_run(loads, router=8)) is not None
    # a sixteenth of the router (the tile loops), no loads, no sizes,
    # loads of nothing
    assert read(_run(loads, router=64)) is None
    assert read(_run(loads, held=64)) is None
    assert read({"results": {"expert_loads": loads}, "sizes": {}}) is None
    assert read(_run([])) is None
    assert read({"results": {}, "sizes": {"num_experts": 4}}) is None
    assert read(_run([np.zeros((1, 4), int)])) is None
    # a program whose kernel takes every tile whole has no such count
    from nnstreamer_tpu.ops import grouped
    monkeypatch.delattr(grouped, "half_tiles")
    assert read(_run(loads)) is None


def test_the_entry_is_the_last_and_lists_both_cells():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "frames_per_s", "workloads": CELLS}
    assert bench["per_layer"][-1] == entry
