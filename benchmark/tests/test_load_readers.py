"""The six ``load.*`` readers on a ``run`` made by hand, and one cell's
traced rehearsal line holding all six (CPU, the configuration's tiny
``rehearsal`` sizes; ``python -m pytest benchmark/tests -q``):

* each reads ``info.counters.transfer.load`` to the digit; a record
  with ``at: "frame"`` (a recompile on the frame path) is no part of
  the load's sums; the five parts add up to ``load.total_s``;
* a program without the block (every parent of PR 36), a block whose
  first buffer is not through, a driver without a ``transfer`` counter:
  None, no raise;
* ``BENCHMARK.json`` lists the six under one layer, moving ``setup_s``,
  in every cell;
* ``run.py --rehearsal --trace 1`` of the ViT cell prints all six, and
  the block names one record a program.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402

CELL = "vit_h14.stream_b32"
METRICS = ("load.total_s", "load.model_s", "load.trace_s", "load.lower_s",
           "load.compile_s", "load.other_s")


def _record(program, at, trace_s, lower_s, compile_s, **more):
    return {"program": program, "signature": "uint8[4]", "donate": [],
            "at": at, "wall_s": 9.0, "trace_s": trace_s, "lower_s": lower_s,
            "compile_s": compile_s, "cache": "hit", "retrieval_s": 0.25,
            "prepare_s": 0.0, **more}


def _run(load):
    transfer = {"window": 4} if load is None else {"window": 4, "load": load}
    return {"counters": {"transfer": transfer}}


BLOCK = {
    "start_s": 3.5, "model_s": 2.25, "place_s": 0.5,
    "first_buffer_s": 12.0, "total_s": 16.0,
    "programs": [
        _record("jit_nns_filter_prepare", "load", 0.125, 0.25, 0.5),
        _record("jit_nns_filter_m", "load", 4.0, 2.0, 3.0, prepare_s=1.0),
        # a recompile on the frame path: after the load, not part of it
        _record("jit_nns_filter_m", "frame", 100.0, 200.0, 400.0),
    ]}
WANT = {"load.total_s": 16.0, "load.model_s": 2.75, "load.trace_s": 4.125,
        "load.lower_s": 2.25, "load.compile_s": 3.5, "load.other_s": 3.375}


@pytest.mark.parametrize("metric", METRICS)
def test_reader_reads_the_block_to_the_digit(metric):
    assert bench_run.load_reader(metric)(_run(BLOCK)) == WANT[metric]


def test_the_five_parts_add_up_to_the_total():
    got = {m: bench_run.load_reader(m)(_run(BLOCK)) for m in METRICS}
    assert sum(got[m] for m in METRICS[1:]) == got["load.total_s"]


@pytest.mark.parametrize("metric", METRICS)
def test_reader_returns_none_where_there_is_nothing_to_read(metric):
    read = bench_run.load_reader(metric)
    assert read(_run(None)) is None                 # a parent's program
    assert read({"counters": {}}) is None           # no transfer counter
    assert read({"counters": {"transfer": None}}) is None
    unfinished = {**BLOCK, "first_buffer_s": None, "total_s": None}
    assert read(_run(unfinished)) is None


def test_benchmark_json_lists_the_six_in_every_cell():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    mine = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == list(METRICS)
    assert mine == bench["per_layer"][-6:]          # added at the end
    for m in mine:
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("s", "lower", "program_counter",
                                "entry + load", "setup_s")
        assert m["workloads"] == cells


def test_a_traced_rehearsal_line_holds_all_six():
    result = bench_run.run_cell(CELL, 7, 3.0, 1, rehearsal=True)
    metrics = result["metrics"]
    assert set(METRICS) <= set(metrics)
    assert all(metrics[m]["unit"] == "s" for m in METRICS)
    value = {m: metrics[m]["value"] for m in METRICS}
    assert sum(value[m] for m in METRICS[1:]) == \
        pytest.approx(value["load.total_s"], abs=1e-9)
    assert min(value[m] for m in METRICS[:5]) > 0
    load = result["info"]["counters"]["transfer"]["load"]
    assert load["total_s"] == value["load.total_s"]
    names = [r["program"] for r in load["programs"]]
    assert names == ["jit_nns_filter_prepare", "jit_nns_filter_vit_h14"]
    assert {r["at"] for r in load["programs"]} == {"load"}
    assert result["failed"] == 0
