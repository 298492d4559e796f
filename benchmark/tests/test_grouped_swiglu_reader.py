"""``kernel.nns_grouped_swiglu.roofline_pct`` on traces made by hand,
and the Trinity cell's program naming the kernel (CPU, the
configuration's tiny ``rehearsal`` sizes; ``python -m pytest
benchmark/tests -q``):

* the kernel's events inside whole filter programs are read by their
  name or by their scope, those outside a filter program or in a
  program cut by the traced stretch's edge are not;
* a trace without them (``ragged_dot``, the tile loops), a family
  without the floor, a run without peaks, too few programs: None, no
  raise;
* the kernel carries ``block/moe/experts``, so ``model_step.
  experts_device_pct`` and ``model_step.moe_device_pct`` read the same;
* a sound rehearsal run of the cell is correct and its
  ``kernel_calls`` name ``nns_grouped_swiglu`` once an expert layer.
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from nnsbench import progtrace  # noqa: E402

CELL = "trinity_mini_pp8_l5.lmstream_s4096"
METRIC = "kernel.nns_grouped_swiglu.roofline_pct"
SLIDING, FULL = "sliding_attention", "full_attention"
SCOPE = "jit(nns_filter_m)/block/"


def _hand_trace(how: str, programs: int = 6):
    """``programs`` filter programs of 100 ns inside the traced stretch,
    each: 20 ns under ``block/moe/route``, 30 in the grouped kernel
    (``how``: ``named`` by the custom call's own name with no scope,
    ``scoped`` under ``block/moe/experts/nns_grouped_swiglu/
    pallas_call:`` with the compiler's name for a custom call,
    ``absent``: a ``ragged-dot`` event in its place), 50 under
    ``block/attn/window``; one more program that starts inside the
    stretch and ends after it, and one kernel event of 40 ns in a
    program that is no filter's."""
    kernel = {
        "named": ["%nns_grouped_swiglu.{i} = f32[8,4] custom-call()", {}],
        "scoped": ["%custom-call.{i} = f32[8,4] custom-call()", {
            "scope": SCOPE + "moe/experts/nns_grouped_swiglu/pallas_call:"}],
        "absent": ["%ragged-dot-none.{i} = f32[8,4] custom-call()", {}],
    }[how]
    modules, ops = [], []
    for i in range(programs + 1):
        t = 1000 + 200 * i
        modules.append(["jit_nns_filter_m(17)", t, 100, {}])
        ops.append(["%fusion.3 = f32[8]", t, 20,
                    {"scope": SCOPE + "moe/route/mul:"}])
        ops.append([kernel[0].format(i=i), t + 20, 30, kernel[1]])
        ops.append(["%fusion.5 = f32[8]", t + 50, 50,
                    {"scope": SCOPE + "attn/window/dot_general:"}])
    end = 1000 + 200 * programs + 60        # cuts the last program
    modules.append(["jit_other(3)", 950, 40, {}])
    ops.append([kernel[0].format(i=99), 950, 40, kernel[1]])
    return progtrace.ProgTrace({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.trace_window", 900, end - 900, {}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]}]})


def _run(**over):
    cfg = dict(family="afmoe", hidden_size=8, moe_intermediate_size=5,
               num_experts=4, num_experts_per_tok=2, expert_parallel=1,
               num_dense_layers=1, layer_types=[SLIDING, FULL, SLIDING])
    run = {"config": cfg, "sizes": {}, "traffic": {"tokens_per_buffer": 3},
           "peaks": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}}
    return {**run, **over}


@pytest.mark.parametrize("how", ["named", "scoped"])
def test_the_kernels_events_inside_whole_programs_are_read(how, monkeypatch):
    """Six whole programs' floor (two expert layers x 1152 B at 1e11
    B/s = 23.04 ns a program, the floor ``kernel.ragged_dot.
    roofline_pct`` divides by) over the 180 ns of the kernel's events
    inside them: the seventh program is cut by the stretch's end and
    the event in ``jit_other`` is no filter's, so neither counts."""
    prog = _hand_trace(how)
    monkeypatch.setattr(progtrace, "of_run", lambda run: prog)
    read = bench_run.load_reader(METRIC)
    assert read(_run()) == pytest.approx(100 * 6 * 23.04 / 180)
    assert bench_run.load_reader("kernel.ragged_dot.roofline_pct")(
        _run()) is None
    # a family without the floor, a run without peaks: nothing, no raise
    cfg = _run()["config"]
    assert read(_run(config=dict(cfg, family="vit"))) is None
    assert read(_run(peaks=None)) is None


def test_a_scoped_kernel_is_in_both_expert_shares(monkeypatch):
    """The kernel's events carry ``block/moe/experts``: ``model_step.
    experts_device_pct`` (scope or the compiler's ``ragged-dot`` name)
    and ``model_step.moe_device_pct`` (scope alone) read the same."""
    prog = _hand_trace("scoped")
    monkeypatch.setattr(progtrace, "of_run", lambda run: prog)
    experts = bench_run.load_reader("model_step.experts_device_pct")(_run())
    assert experts == pytest.approx(
        bench_run.load_reader("model_step.moe_device_pct")(_run()))
    assert experts == pytest.approx(100 * prog.scope_share("block/moe"))


@pytest.mark.parametrize("how,programs", [("absent", 6), ("named", 3)],
                         ids=["no_such_event", "too_few_programs"])
def test_nothing_to_read_is_none(how, programs, monkeypatch):
    """A program that states the product otherwise (``ragged_dot``, the
    tile loops, any parent of PR 35) and a stretch that holds fewer
    whole programs than a reader trusts: None, and no raise."""
    prog = _hand_trace(how, programs)
    monkeypatch.setattr(progtrace, "of_run", lambda run: prog)
    assert bench_run.load_reader(METRIC)(_run()) is None
    monkeypatch.setattr(progtrace, "of_run", lambda run: None)
    assert bench_run.load_reader(METRIC)(_run()) is None


def test_sound_run_names_the_grouped_kernel():
    res = bench_run.run_cell(CELL, 35, 1.5, 0, rehearsal=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    report = res["info"]["counters"]["transfer"]
    # 5 layers x 1 block of the 64 rehearsal tokens; 4 expert layers
    assert report["kernel_calls"] == {"nns_masked_attention": 5,
                                      "nns_grouped_swiglu": 4}
    assert report["prepared_equations"] == 5 * 8 + 4
