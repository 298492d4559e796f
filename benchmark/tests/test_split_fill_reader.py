"""``moe.split_fill_pct`` on loads made by hand (CPU; ``python -m pytest
benchmark/tests -q``):

* the pairs served over the rows the split form multiplies, all layers
  of all arrived buffers together, at the program's own tile: a tile an
  expert's tile, half of one where an expert's last tile holds at most
  half its rows;
* the rows are ``ops.grouped.tiles_walked`` and ``half_tiles``, the
  counts the kernels take their steps by;
* no loads, loads of nothing, loads of a chip that takes the
  whole-router kernel (half its router or more), a program without the
  split form: None, no raise;
* the entry stands in ``BENCHMARK.json``'s ``per_layer`` and lists the
  three cells whose chips hold less than half their router.
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

METRIC = "moe.split_fill_pct"
CELLS = ["glm5_ep16_l5.lmstream_s4096", "longcat_ep32_l4.lmstream_s4096",
         "dots3_ep8_l5.lmstream_s8192"]


def _run(loads, held=4, router=32):
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


@pytest.mark.parametrize("loads,pairs,rows", [
    # last tiles of 4 (half: 4 rows), 5 (one row over: 8), 1 (4) and a
    # whole tile
    ([[[12, 5, 1, 8]]], 26, 8 + 4 + 8 + 4 + 8),
    # every expert's rows in one half tile, one nobody chose
    ([[[4, 3, 0, 2]]], 9, 12),
    # whole tiles only
    ([[[16, 8, 0, 24]]], 48, 48),
    # two buffers of two layers, summed and not averaged
    ([[[9, 0, 0, 0], [4, 4, 4, 4]], [[13, 3, 0, 0], [0, 0, 0, 20]]], 61,
     12 + 16 + 16 + 4 + 20),
], ids=["mixed", "half_tiles_only", "whole_tiles", "buffers_and_layers"])
def test_pairs_over_the_rows_multiplied(loads, pairs, rows, tile):
    read = bench_run.load_reader(METRIC)
    assert read(_run([np.asarray(b) for b in loads])) == pytest.approx(
        100.0 * pairs / rows)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_rows_are_the_kernels_own_count(seed):
    """At the program's tile (256 rows), on a skewed routing of 32 of 256
    experts: the rows are a tile for each tile ``tiles_walked`` counts,
    less half a tile for each ``half_tiles`` counts, and ``half_tiles``
    is the number of experts whose ``count % tile`` lies in ``(0, tile
    // 2]``."""
    from nnstreamer_tpu.models.latent import EXPERT_TILE
    from nnstreamer_tpu.ops.grouped import half_tiles, tiles_walked
    rng = np.random.default_rng(seed)
    counts = rng.multinomial(8192, rng.dirichlet(np.ones(32) * 8),
                             size=(2, 4))
    rest = counts % EXPERT_TILE
    halves = int(((rest > 0) & (rest <= EXPERT_TILE // 2)).sum())
    assert int(np.sum(half_tiles(counts, EXPERT_TILE))) == halves > 0
    rows = EXPERT_TILE * int(np.sum(-(-counts // EXPERT_TILE))) \
        - EXPERT_TILE // 2 * halves
    assert rows == EXPERT_TILE * int(np.sum(tiles_walked(
        counts, EXPERT_TILE))) - EXPERT_TILE // 2 * halves
    read = bench_run.load_reader(METRIC)
    assert read(_run(list(counts), held=32, router=256)) == pytest.approx(
        100.0 * counts.sum() / rows)


@pytest.mark.parametrize("sizes,loads", [
    ({"n_routed_experts": 4, "n_routed_experts_total": 64},
     [[[12, 5, 1, 8]]]),
    # identity experts' pairs in the load's last column, the router
    # their number wider
    ({"n_routed_experts": 4, "n_routed_experts_total": 64,
      "zero_expert_num": 32}, [[[12, 5, 1, 8, 40]]]),
], ids=["n_routed", "identity_column"])
def test_the_configurations_own_names(sizes, loads, tile):
    """The GLM-5 and dots3 files name the experts ``n_routed_experts``
    of ``n_routed_experts_total``; LongCat's adds ``zero_expert_num``
    identity experts, whose pairs the program counts in a last column
    and no kernel serves."""
    read = bench_run.load_reader(METRIC)
    run = {"results": {"expert_loads": [np.asarray(b) for b in loads]},
           "sizes": sizes}
    assert read(run) == pytest.approx(100.0 * 26 / 32)
    # 4 of a router of 8 is half of it: the kernel's
    run["sizes"] = {**sizes, "n_routed_experts_total": 8,
                    "zero_expert_num": 0}
    run["results"]["expert_loads"] = [np.asarray([[12, 5, 1, 8]])]
    assert read(run) is None


def test_nothing_to_read_is_none(monkeypatch):
    read = bench_run.load_reader(METRIC)
    loads = [np.asarray([[8, 9, 0, 7]])]
    assert read(_run(loads)) is not None
    # half a router held or the whole of it (the kernel's path), a chip
    # that names no router, no loads, no sizes, loads of nothing
    assert read(_run(loads, router=8)) is None
    assert read(_run(loads, router=4)) is None
    assert read(_run(loads, router=None)) is None
    assert read(_run(loads, held=64)) is None
    assert read({"results": {"expert_loads": loads}, "sizes": {}}) is None
    assert read(_run([])) is None
    assert read({"results": {}, "sizes": {"num_experts": 4}}) is None
    assert read(_run([np.zeros((1, 4), int)])) is None
    # a program without the split form (the tile loops there) has no rule
    from nnstreamer_tpu.ops import grouped
    monkeypatch.delattr(grouped, "takes_split")
    assert read(_run(loads)) is None


def test_the_entry_lists_the_cells_under_half_a_router():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "frames_per_s", "workloads": CELLS}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert set(CELLS) <= set(cells)
