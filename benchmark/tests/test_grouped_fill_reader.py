"""``moe.grouped_fill_pct`` on loads made by hand, and on a rehearsal
run of the Trinity cell (CPU; ``python -m pytest benchmark/tests -q``):

* pairs served over the rows of ``sum(ceil(count / tile))`` tiles, all
  layers of all arrived buffers together, by the program's own
  ``tiles_walked`` at the program's own tile;
* a program without the function (a parent of PR 37), loads that are
  not the whole router's, no loads, loads of nothing: None, no raise;
* the entry in ``BENCHMARK.json`` lists the Trinity cell alone and
  moves ``frames_per_s``.
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

CELL = "trinity_mini_pp8_l5.lmstream_s4096"
METRIC = "moe.grouped_fill_pct"


def _run(loads, num_experts=4):
    return {"results": {"expert_loads": loads},
            "sizes": {"num_experts": num_experts}}


@pytest.fixture
def tile(monkeypatch):
    """The program's tile cut to 8 rows, so that loads written by hand
    cross it."""
    from nnstreamer_tpu.models import latent
    monkeypatch.setattr(latent, "EXPERT_TILE", 8)
    return 8


@pytest.mark.parametrize("loads,tiles", [
    # one buffer, one layer: 1 + 2 + 0 + 1 tiles for 24 pairs
    ([[[8, 9, 0, 7]]], 4),
    # every expert a whole number of tiles: nothing multiplied in vain
    ([[[16, 8, 0, 24]]], 6),
    # every expert one pair: a tile each
    ([[[1, 1, 1, 1]]], 4),
    # two buffers of two layers, summed and not averaged
    ([[[24, 0, 0, 0], [6, 6, 6, 6]], [[1, 23, 0, 0], [0, 0, 0, 24]]], 14),
], ids=["mixed", "whole_tiles", "one_pair_each", "buffers_and_layers"])
def test_pairs_served_over_rows_multiplied(loads, tiles, tile):
    read = bench_run.load_reader(METRIC)
    pairs = int(np.sum(loads))
    assert read(_run([np.asarray(b) for b in loads])) == pytest.approx(
        100.0 * pairs / (tiles * tile))


def test_the_tile_is_the_programs_own():
    """At the program's tile (256 rows) 128 experts of 256 pairs fill
    their tiles, and of 257 just over half of two."""
    from nnstreamer_tpu.models.latent import EXPERT_TILE
    read = bench_run.load_reader(METRIC)
    even = np.full((1, 4, 128), EXPERT_TILE)
    assert read(_run([even[0]], 128)) == pytest.approx(100.0)
    assert read(_run([even[0] + 1], 128)) == pytest.approx(
        100.0 * (EXPERT_TILE + 1) / (2 * EXPERT_TILE))


def test_nothing_to_read_is_none(monkeypatch):
    read = bench_run.load_reader(METRIC)
    loads = [np.asarray([[8, 9, 0, 7]])]
    assert read(_run(loads)) is not None
    # a share of the router (the tile loops), no loads, loads of nothing
    assert read(_run(loads, num_experts=64)) is None
    assert read({"results": {"expert_loads": loads}, "sizes": {}}) is None
    assert read(_run([])) is None
    assert read({"results": {}, "sizes": {"num_experts": 4}}) is None
    assert read(_run([np.zeros((1, 4), int)])) is None
    # a program whose walk has no count of its own: every parent of PR 37
    from nnstreamer_tpu.ops import grouped
    monkeypatch.delattr(grouped, "tiles_walked")
    assert read(_run(loads)) is None


def test_the_entry_lists_the_trinity_cell_alone():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "frames_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1] == entry


def test_a_rehearsal_run_reports_it(monkeypatch):
    """The cell's tiny sizes (64 tokens, a router 16 wide choosing 4):
    the traced line holds the metric, the pairs of the loads it was
    read from over the program's own count of tiles."""
    from nnstreamer_tpu.models.latent import EXPERT_TILE
    res = bench_run.run_cell(CELL, 37, 1.5, 1, rehearsal=True)
    assert res["correct"], res["checks"]
    got = res["metrics"][METRIC]
    assert got["unit"] == "%"
    # 64 x 4 pairs a layer over at most 16 experts, each under a tile
    assert 100.0 * 256 / (16 * EXPERT_TILE) <= got["value"] <= 100.0
