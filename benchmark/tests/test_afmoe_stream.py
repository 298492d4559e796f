"""The ``trinity_mini_pp8_l5.lmstream_s4096`` cell's comparison has been
shown to fail, and its cost arithmetic holds (CPU, the configuration's
tiny ``rehearsal`` sizes; ``python -m pytest benchmark/tests -q``):

* a sound run is correct, and the plain reference computed in fp8 (both
  kinds) in the program's place is not, on three seeds;
* a run whose last row or ``logprobs`` are rolled, whose load loses a
  column, whose routed experts' part is zeroed where it is produced, or
  whose window is dropped (every layer full) comes out ``correct:
  false``;
* ``nnsbench/costs_afmoe.py`` against hand-worked counts and the
  issue's;
* the readers of the compiler's unscoped ``ragged-dot`` events on a
  trace made by hand;
* ``tools/route_diag.py`` finds the sequence whose last token the
  program routes otherwise than the reference.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from nnsbench import costs_afmoe, progtrace  # noqa: E402

CELL = "trinity_mini_pp8_l5.lmstream_s4096"
SLIDING, FULL = "sliding_attention", "full_attention"


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 77])
def test_sound_run_is_correct_and_control_is_not(seed):
    res = bench_run.run_cell(CELL, seed, 1.5, 0, rehearsal=True,
                             control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["info"]["compared"]["buffers_compared"] > 0
    report = res["info"]["counters"]["transfer"]
    assert report["prepared_leaves"] == 0
    # 5 layers x 1 block of the 64 rehearsal tokens
    assert report["kernel_calls"] == {"nns_masked_attention": 5}
    # a layer's four projections reshaped and transposed a head at a
    # time, an expert layer's router bias to float32
    assert report["prepared_equations"] == 5 * 8 + 4
    assert not any(res["info"]["control_correct"].values()), (
        "an fp8 control passed the cell's limits", res["info"]["control"])


def _roll_last_row(apply_fn):
    """The last position's logits handed on one class late."""
    import jax.numpy as jnp

    def broken(params, tokens):
        last, logprobs, load = apply_fn(params, tokens)
        return jnp.roll(last, 1), logprobs, load
    return broken


def _roll_logprobs(apply_fn):
    """Every log-probability handed on one position late."""
    import jax.numpy as jnp

    def broken(params, tokens):
        last, logprobs, load = apply_fn(params, tokens)
        return last, jnp.roll(logprobs, 1), load
    return broken


def _drop_load_column(apply_fn):
    """The first held expert reports no pair."""
    def broken(params, tokens):
        last, logprobs, load = apply_fn(params, tokens)
        return last, logprobs, load.at[:, 0].set(0)
    return broken


def _zero_routed_experts(apply_fn):
    """The held experts' last product gives nothing: the routed part is
    0 where it is produced; the router, the shared expert and the first
    expert layer's load as they were."""
    def broken(params, tokens):
        layers = [dict(layer, moe=dict(layer["moe"], experts=dict(
            layer["moe"]["experts"],
            w2=layer["moe"]["experts"]["w2"] * 0)))
            if "moe" in layer else layer for layer in params["layers"]]
        return apply_fn(dict(params, layers=layers), tokens)
    return broken


def _drop_the_window(apply_fn):
    """Every layer attends every earlier key (the rotation stays): the
    program as it would be if the kernel ignored its ``window``."""
    from nnstreamer_tpu.models import latent

    def broken(params, tokens):
        real = latent.blocked_causal_attention
        latent.blocked_causal_attention = \
            lambda *a, window=None, **kw: real(*a, **kw)
        try:
            return apply_fn(params, tokens)
        finally:
            latent.blocked_causal_attention = real
    return broken


@pytest.mark.parametrize("fault,numbers", [
    (_roll_last_row, ["logit_rms", "logit_gap"]),
    (_roll_logprobs, ["logprob_rms"]),
    (_drop_load_column, ["load_l1"]),
    (_zero_routed_experts, ["logprob_rms"]),
    (_drop_the_window, ["logprob_rms"])],
    ids=["row_rolled", "logprobs_rolled", "load_column", "experts_zeroed",
         "window_dropped"])
def test_altered_output_is_not_correct(fault, numbers):
    res = bench_run.run_cell(CELL, 5, 1.5, 0, rehearsal=True, fault=fault)
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
    over = [k for k, n in res["checks"].items() if n["value"] > n["limit"]]
    assert set(numbers) <= set(over), res["checks"]


def _published():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "trinity_mini_pp8_l5.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_issues():
    cfg = _published()
    # q and gate 2048 x 4096 each, k and v 2048 x 512 each, o 4096 x 2048
    assert costs_afmoe.attention_params(cfg) == (
        2048 * (4096 + 512 + 512 + 4096) + 4096 * 2048) == 27_262_976
    assert costs_afmoe.expert_params(cfg) == 3 * 2048 * 1024 == 6_291_456
    assert costs_afmoe.held_experts_per_token(cfg) == 8
    # the file's reckoned_bytes: an expert layer 839.1 M, the dense layer
    # 65.0 M, the two vocabulary slices 102.5 M
    expert_layer = 27_262_976 + 2048 * 128 + 6_291_456 + 128 * 6_291_456
    assert expert_layer == 839_122_944
    dense_layer = 27_262_976 + 3 * 2048 * 6144
    assert dense_layer == 65_011_712
    total = 4 * expert_layer + dense_layer + 2 * 25024 * 2048
    assert total == 3_524_001_792 and round(total * 2 / 16e9, 2) == 0.44
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "vocab_size"]
    assert cfg["num_experts"] == 128 and cfg["expert_parallel"] == 1


def test_sequence_flops_by_hand():
    """A model small enough to count on paper: d 8, 4 heads on 2 of 3,
    dense width 6, 4 experts of width 5 all held, 2 chosen a token, one
    shared; window 2; vocabulary 10; S 3; a dense sliding layer, then a
    full and a sliding expert layer."""
    cfg = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
               head_dim=3, intermediate_size=6, moe_intermediate_size=5,
               num_experts=4, num_experts_per_tok=2, num_shared_experts=1,
               expert_parallel=1, sliding_window=2, num_dense_layers=1,
               layer_types=[SLIDING, FULL, SLIDING], vocab_size=10)
    attn = 8 * 3 * (3 * 4 + 2 * 2)                                  # 384
    assert costs_afmoe.attention_params(cfg) == attn == 384
    assert costs_afmoe.expert_params(cfg) == 120
    # positions 0, 1, 2 keep 1, 2, 2 keys under a window of 2; 1, 2, 3 full
    assert costs_afmoe.kept_pairs(cfg, 3, SLIDING) == 5
    assert costs_afmoe.kept_pairs(cfg, 3, FULL) == 6
    dense = 2 * (3 * (attn + 3 * 8 * 6) + 5 * 4 * 2 * 3)           # 3408
    assert costs_afmoe.layer_flops(cfg, 3, SLIDING, False) == dense == 3408
    per_token = attn + 8 * 4 + (1 + 2) * 120                        # 776
    full = 2 * (3 * per_token + 6 * 4 * 2 * 3)                      # 4944
    slide = 2 * (3 * per_token + 5 * 4 * 2 * 3)                     # 4896
    assert costs_afmoe.layer_flops(cfg, 3, FULL, True) == full == 4944
    assert costs_afmoe.layer_flops(cfg, 3, SLIDING, True) == slide == 4896
    assert costs_afmoe.sequence_flops(cfg, 3) \
        == dense + full + slide + 2 * 3 * 8 * 10
    # half the experts held: one of a token's two choices on average
    assert costs_afmoe.held_experts_per_token(
        dict(cfg, expert_parallel=2)) == 1.0
    # the kernel's floor: q.k and p.v of the kept pairs every query
    # head; q and o a query head, k and v a key/value head, in bfloat16
    assert costs_afmoe.attention_flops(cfg, 3, SLIDING) == 4 * 5 * 4 * 3
    assert costs_afmoe.attention_bytes(cfg, 3) == 2 * 3 * 3 * 2 * (4 + 2)
    peaks = {"flops_bf16": 10.0, "hbm_bytes_per_s": 1000.0}
    assert costs_afmoe.attention_layer_floor_s(cfg, 3, FULL, peaks) == 28.8
    assert costs_afmoe.attention_layer_floor_s(
        cfg, 3, FULL, dict(peaks, hbm_bytes_per_s=1.0)) == 216.0
    # a sequence: two sliding layers and a full one
    assert costs_afmoe.attention_floor_s(cfg, 3, peaks) == 2 * 24.0 + 28.8
    # the routed experts' grouped product, an expert layer: 3 tokens x 2
    # chosen x 120 multiply-adds; 4 experts' 120 weights once and a row
    # of 8 in and out a pair, in bfloat16; two expert layers a sequence
    assert costs_afmoe.held_experts(cfg) == 4
    assert costs_afmoe.grouped_flops(cfg, 3) == 2 * 3 * 2 * 120 == 1440
    assert costs_afmoe.grouped_bytes(cfg, 3) == 2 * (4 * 120 + 2 * 6 * 8) \
        == 1152
    assert costs_afmoe.grouped_floor_s(cfg, 3, peaks) == 2 * 144.0
    assert costs_afmoe.grouped_floor_s(
        cfg, 3, dict(peaks, hbm_bytes_per_s=1.0)) == 2 * 1152.0
    # half the router held: half the weights, half a token's pairs
    half = dict(cfg, expert_parallel=2)
    assert costs_afmoe.held_experts(half) == 2
    assert costs_afmoe.grouped_flops(half, 3) == 720
    assert costs_afmoe.grouped_bytes(half, 3) == 2 * (2 * 120 + 2 * 3 * 8)


def test_cell_flops_are_the_issues():
    """ISSUE 34's arithmetic, a sequence of 4096: 452.85 M multiply-adds
    a token, window pairs 6,292,480 and causal pairs 8,390,656, 4.26
    TFLOP; routed experts 39 %, shared 5, head 10, the dense MLP 7, the
    attention's scores 13 %."""
    cfg = _published()
    s, tera = 4096, 1e12
    assert costs_afmoe.kept_pairs(cfg, s, SLIDING) == 6_292_480
    assert costs_afmoe.kept_pairs(cfg, s, FULL) == 8_390_656
    per_token = 4 * 84_148_224 + 65_011_712 + 51_249_152
    assert round(per_token / 1e6, 2) == 452.85
    pairs = (4 * 6_292_480 + 8_390_656) * 32 * 256
    total = costs_afmoe.sequence_flops(cfg, s)
    assert total == 2.0 * (s * per_token + pairs)
    assert round(total / tera, 2) == 4.26

    def share(macs):
        return round(100 * 2 * macs / total)

    assert share(4 * s * 8 * 6_291_456) == 39
    assert share(4 * s * 6_291_456) == 5
    assert share(s * 51_249_152) == 10
    assert share(s * 3 * 2048 * 6144) == 7
    assert share(pairs) == 13
    # the kernel's roofline a layer: compute-bound (0.10 / 0.13 TFLOP
    # against 75 MB); at the peak a sequence's attention takes 2.8 ms
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    floors = [costs_afmoe.attention_layer_floor_s(cfg, s, k, peaks)
              for k in cfg["layer_types"]]
    assert [round(f * 1e6) for f in floors] == [523, 523, 698, 523, 523]
    assert costs_afmoe.attention_floor_s(cfg, s, peaks) == sum(floors)
    assert costs_afmoe.attention_bytes(cfg, s) == 75_497_472
    # the grouped product an expert layer: 32,768 pairs x 6,291,456
    # multiply-adds = 0.412 TFLOP, 2.09 ms at the peak; 128 experts'
    # 1.61 GB once and 32,768 rows of 2048 in and out, 1.88 GB, 2.29 ms
    # at the memory's rate: the bytes bind, just (256 rows an expert is
    # the chip's ridge), 9.2 ms a sequence
    assert costs_afmoe.grouped_flops(cfg, s) == 2.0 * 32_768 * 6_291_456
    assert costs_afmoe.grouped_bytes(cfg, s) == 2.0 * (
        128 * 6_291_456 + 2 * 32_768 * 2048) == 1_879_048_192
    assert round(costs_afmoe.grouped_flops(cfg, s) / 197e12 * 1e6) == 2093
    assert round(costs_afmoe.grouped_floor_s(cfg, s, peaks) * 1e6) \
        == 4 * 2294 + 1
    # under 100 % at any rate the chip could reach
    assert round(total / 197e12 * 1e3, 1) == 21.6


def _hand_trace(kernel: bool):
    """Six programs of 100 ns, each: 20 ns under ``block/moe/route``, 30
    in a ``ragged-dot`` event the compiler left without the model's
    scope (or, ``kernel`` false, 30 more under ``block/moe/experts``), 50
    under ``block/attn/window``; and one ``ragged-dot`` of 40 ns outside
    any filter program."""
    scope = "jit(nns_filter_m)/block/"
    modules, ops = [], []
    for i in range(6):
        t = 1000 + 200 * i
        modules.append(["jit_nns_filter_m(17)", t, 100, {}])
        ops.append(["%fusion.3 = f32[8]", t, 20,
                    {"scope": scope + "moe/route/mul:"}])
        ops.append([f"%ragged-dot-none.{i} = f32[8,4] custom-call()",
                    t + 20, 30, {"scope": "ragged-dot-none:"}] if kernel
                   else ["%fusion.4 = f32[8]", t + 20, 30,
                         {"scope": scope + "moe/experts/dot_general:"}])
        ops.append(["%fusion.5 = f32[8]", t + 50, 50,
                    {"scope": scope + "attn/window/dot_general:"}])
    modules.append(["jit_other(3)", 2300, 40, {}])
    ops.append(["%ragged-dot-none.9 = f32[8,4] custom-call()", 2300, 40, {}])
    return progtrace.ProgTrace({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.trace_window", 900, 1500, {}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]}]})


@pytest.mark.parametrize("kernel", [True, False], ids=["ragged", "scoped"])
def test_the_unscoped_grouped_product_is_read_by_its_name(kernel,
                                                          monkeypatch):
    """``model_step.experts_device_pct`` counts the ``ragged-dot`` events
    inside the filter's programs with ``block/moe`` (50 of each 100 ns;
    the one outside counts in the whole only: 300 of 640), where
    ``scope_share`` reads 120 of 640; with the product scoped, both read
    the same. ``kernel.ragged_dot.roofline_pct`` is six programs' floor
    over the 180 ns of those events, not capped, and None without
    them."""
    prog = _hand_trace(kernel)
    monkeypatch.setattr(progtrace, "of_run", lambda run: prog)
    cfg = dict(family="afmoe", hidden_size=8, moe_intermediate_size=5,
               num_experts=4, num_experts_per_tok=2, expert_parallel=1,
               num_dense_layers=1, layer_types=[SLIDING, FULL, SLIDING])
    run = {"config": cfg, "sizes": {}, "traffic": {"tokens_per_buffer": 3},
           "peaks": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}}
    share = bench_run.load_reader("model_step.experts_device_pct")(run)
    roofline = bench_run.load_reader("kernel.ragged_dot.roofline_pct")(run)
    assert share == pytest.approx(100 * 300 / 640)
    if kernel:
        assert 100 * prog.scope_share("block/moe") \
            == pytest.approx(100 * 120 / 640)
        # two expert layers x 1152 B at 1e11 B/s = 23.04 ns a program
        assert roofline == pytest.approx(100 * 6 * 23.04 / 180)
    else:
        assert 100 * prog.scope_share("block/moe") == pytest.approx(share)
        assert roofline is None
    # a family without the floor, a run without peaks: nothing, no raise
    assert bench_run.load_reader("kernel.ragged_dot.roofline_pct")(
        dict(run, config=dict(cfg, family="vit"))) is None
    assert bench_run.load_reader("kernel.nns_masked_attention.roofline_pct")(
        dict(run, config=dict(cfg, family="vit"))) is None
    assert bench_run.load_reader("kernel.ragged_dot.roofline_pct")(
        dict(run, peaks=None)) is None


def test_route_diag_finds_the_last_token_routed_otherwise():
    """Seed 77 at rehearsal sizes: in the last expert layer the program
    gives the last token of check sequence 0 expert 6 where the
    reference gives it 5 (their biased scores 0.0035 apart); that
    token's stream parts from the reference's there and nowhere before,
    and the row reads ten times the other sequence's ``logit_rms``, whose
    last token is routed alike in every layer."""
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "..", "tools", "route_diag.py"),
         "--workload", CELL, "--seeds", "77", "--rehearsal"],
        capture_output=True, text=True, timeout=600, check=True).stdout
    flipped, alike = [json.loads(line.split(" ", 1)[1])
                      for line in out.splitlines()
                      if line.startswith("REHEARSAL {")]
    assert flipped["last_token_routed_otherwise"] == [False] * 3 + [True]
    assert flipped["last_token_only_program"][3] == [6]
    assert flipped["last_token_only_reference"][3] == [5]
    assert flipped["last_token_choice_edge"][3] < 0.005
    gaps = flipped["last_token_stream_gap"]
    assert max(gaps[:4]) < 0.02 and gaps[4] > 0.2
    assert not any(alike["last_token_routed_otherwise"])
    assert max(alike["last_token_stream_gap"]) < 0.04
    assert flipped["logit_rms"] > 5 * alike["logit_rms"]
