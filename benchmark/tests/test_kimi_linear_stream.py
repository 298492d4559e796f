"""The ``kimi_linear_ep2_l5.lmstream_s8192`` cell's comparison has been
shown to fail, its cost arithmetic holds and its readers read (CPU, the
configuration's tiny ``rehearsal`` sizes; ``python -m pytest
benchmark/tests -q``):

* a sound run is correct, and the plain reference computed in fp8 (both
  kinds) in the program's place is not, on three seeds;
* a run whose last row or ``logprobs`` are rolled, whose load loses a
  column, whose recurrence loses its decay (``a = 0``) or whose routed
  experts' part is zeroed where it is produced comes out ``correct:
  false``;
* ``nnsbench/costs_kimi_linear.py`` against hand-worked counts and the
  issue's;
* the three new readers on a trace made by hand, and None on a program
  without the scope.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from nnsbench import costs_kimi_linear as costs, progtrace  # noqa: E402

CELL = "kimi_linear_ep2_l5.lmstream_s8192"


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 77])
def test_sound_run_is_correct_and_control_is_not(seed):
    res = bench_run.run_cell(CELL, seed, 1.5, 0, rehearsal=True,
                             control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["info"]["compared"]["buffers_compared"] > 0
    report = res["info"]["counters"]["transfer"]
    assert report["prepared_leaves"] == 0
    # 4 KDA layers' two kernels, the MLA layer x 1 block of the 64
    # rehearsal tokens, half a router through the grouped kernel in each
    # of the 4 expert layers
    assert report["kernel_calls"] == {
        "nns_kda_chunk_intra": 4, "nns_kda_chunk_state": 4,
        "nns_masked_attention": 1, "nns_grouped_swiglu": 4}
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


def _with_layers(params, change):
    return dict(params, layers=[change(layer) for layer in params["layers"]])


def drop_the_decay(apply_fn):
    """``a = 0``: the recurrence forgets nothing (a ``dt_bias`` far
    below zero, where softplus gives 0 whatever the gate adds)."""
    def no_decay(layer):
        a = layer["attn"]
        if "dt_bias" not in a:
            return layer
        return dict(layer, attn=dict(a, dt_bias=a["dt_bias"] * 0 - 1e30))

    def broken(params, tokens):
        return apply_fn(_with_layers(params, no_decay), tokens)
    return broken


def zero_routed_experts(apply_fn):
    """The held experts' last product gives nothing: the routed part is
    0 where it is produced; the router, the shared expert and the first
    expert layer's load as they were."""
    def no_experts(layer):
        if "moe" not in layer:
            return layer
        e = layer["moe"]["experts"]
        return dict(layer, moe=dict(layer["moe"],
                                    experts=dict(e, w2=e["w2"] * 0)))

    def broken(params, tokens):
        return apply_fn(_with_layers(params, no_experts), tokens)
    return broken


@pytest.mark.parametrize("fault,numbers", [
    (_roll_last_row, ["logit_rms", "logit_gap"]),
    (_roll_logprobs, ["logprob_rms"]),
    (_drop_load_column, ["load_l1"]),
    (drop_the_decay, ["logprob_rms"]),
    (zero_routed_experts, ["logprob_rms"])],
    ids=["row_rolled", "logprobs_rolled", "load_column", "decay_dropped",
         "experts_zeroed"])
def test_altered_output_is_not_correct(fault, numbers):
    res = bench_run.run_cell(CELL, 5, 1.5, 0, rehearsal=True, fault=fault)
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
    over = [k for k, n in res["checks"].items() if n["value"] > n["limit"]]
    assert set(numbers) <= set(over), res["checks"]


def _published():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "kimi_linear_ep2_l5.json")) as f:
        return json.load(f)


def test_parameter_counts_are_the_files():
    cfg = _published()
    # q, k, v and o 2304 x 4096 each, the two gates through 128, beta
    # 2304 x 32, three convolutions of 4 taps over 4096 channels
    assert costs.kda_params(cfg) == (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
        + 3 * 4 * 4096) == 39_510_016
    # the file's count adds the vectors: A_log 32, dt_bias 4096, the
    # output norm 128
    assert 39_510_016 + 32 + 4096 + 128 == 39_514_272
    assert costs.mla_params(cfg) == (2304 * 6144 + 2304 * 576 + 512 * 8192
                                     + 4096 * 2304) == 29_114_368
    assert costs.expert_params(cfg) == 3 * 2304 * 1024 == 7_077_888
    assert costs.held_experts_per_token(cfg) == 4
    assert (costs.kda_layers(cfg), costs.mla_layers(cfg),
            costs.moe_layers(cfg)) == (4, 1, 4)
    # the file's reckoned_bytes
    router, norms = 2304 * 256 + 256, 2 * 2304
    kda_layer = 39_514_272 + 128 * 7_077_888 + router + 7_077_888 + norms
    mla_layer = 29_114_368 + 512 + 128 * 7_077_888 + router + 7_077_888 \
        + norms
    assert (kda_layer, mla_layer) == (953_156_512, 942_757_120)
    dense_layer = 39_514_272 + 3 * 2304 * 9216 + norms
    assert dense_layer == 103_219_872
    total = 3 * kda_layer + mla_layer + dense_layer + 2 * 20480 * 2304 + 2304
    assert total == 3_999_820_672 and round(total * 2 / 16e9, 2) == 0.5
    assert cfg["reduced"] == ["num_hidden_layers", "linear_attn_config",
                              "num_experts", "vocab_size"]
    assert (cfg["num_experts"], cfg["num_experts_total"],
            cfg["expert_parallel"]) == (128, 256, 2)
    assert cfg["kda_num_heads"] == cfg["linear_attn_config"]["num_heads"]
    assert cfg["kda_head_dim"] == cfg["linear_attn_config"]["head_dim"]


def test_sequence_flops_by_hand():
    """A model small enough to count on paper: d 8; 2 KDA heads of 3,
    4 taps; MLA 2 heads, latent 4, nope 3 + shared 2, v 3; dense width
    6; router 4 wide choosing 2, 2 held, experts of width 5, one shared;
    vocabulary 10; S 3; a dense KDA layer, then an MLA and a KDA expert
    layer."""
    cfg = dict(hidden_size=8, kda_num_heads=2, kda_head_dim=3,
               linear_attn_config=dict(kda_layers=[1, 3],
                                       full_attn_layers=[2],
                                       short_conv_kernel_size=4),
               num_attention_heads=2, kv_lora_rank=4, qk_nope_head_dim=3,
               qk_rope_head_dim=2, v_head_dim=3, intermediate_size=6,
               moe_intermediate_size=5, num_experts=2, num_experts_total=4,
               num_experts_per_token=2, num_shared_experts=1,
               first_k_dense_replace=1, num_hidden_layers=3, vocab_size=10)
    # q, k, v, o 8 x 6 each; two gates 8 x 3 + 3 x 6; beta 8 x 2; taps
    # 3 x 4 x 6
    kda = 4 * 48 + 2 * (24 + 18) + 16 + 72
    assert costs.kda_params(cfg) == kda == 364
    # 7 dk dv a token and head: 3 tokens x 2 heads x 7 x 9
    assert costs.kda_core_flops(cfg, 3) == 378
    # q, k, v, o 3 x 2 x 3 each in bfloat16; the decays 3 x 2 x 3 and
    # beta 3 x 2 in float32
    assert costs.kda_core_bytes(cfg, 3) == 2 * 4 * 18 + 4 * (18 + 6) == 240
    mla = 8 * 2 * 5 + 8 * (4 + 2) + 4 * 2 * 6 + 2 * 3 * 8
    assert costs.mla_params(cfg) == mla == 224
    # causal pairs 6, both heads, q.k over 5 and p.v over 3
    assert costs.attention_flops(cfg, 3) == 2 * 6 * 2 * 8 == 192
    assert costs.attention_bytes(cfg, 3) == 2 * 3 * 2 * 2 * 8 == 192
    assert costs.expert_params(cfg) == 120
    assert costs.held_experts_per_token(cfg) == 1.0
    dense = 2 * 3 * 3 * 8 * 6                                       # 864
    moe = 2 * 3 * (8 * 4 + (1 + 1) * 120)                          # 1632
    assert costs.ffn_flops(cfg, 3, False) == dense == 864
    assert costs.ffn_flops(cfg, 3, True) == moe == 1632
    assert costs.sequence_flops(cfg, 3) == (
        2 * (2 * 3 * kda + 378) + 2 * 3 * mla + 192
        + dense + 2 * moe + 2 * 3 * 8 * 10)
    peaks = {"flops_bf16": 10.0, "hbm_bytes_per_s": 1000.0}
    # the recurrence's floor: two KDA layers, the operations bind at
    # these toy peaks, the bytes where the memory is slow
    assert costs.kda_floor_s(cfg, 3, peaks) == 2 * 37.8
    assert costs.kda_floor_s(cfg, 3, dict(peaks, hbm_bytes_per_s=1.0)) \
        == 2 * 240.0
    assert costs.attention_floor_s(cfg, 3, peaks) == 19.2
    assert costs.attention_floor_s(
        cfg, 3, dict(peaks, hbm_bytes_per_s=1.0)) == 192.0
    # the routed experts' grouped product, an expert layer: 3 tokens x 1
    # held choice x 120 multiply-adds; 2 experts' 120 weights once and a
    # row of 8 in and out a pair, in bfloat16; two expert layers
    assert costs.grouped_flops(cfg, 3) == 2 * 3 * 120 == 720
    assert costs.grouped_bytes(cfg, 3) == 2 * (2 * 120 + 2 * 3 * 8) == 576
    assert costs.grouped_floor_s(cfg, 3, peaks) == 2 * 72.0
    assert costs.grouped_floor_s(
        cfg, 3, dict(peaks, hbm_bytes_per_s=1.0)) == 2 * 576.0


def test_cell_flops_are_the_issues():
    """ISSUE 38's arithmetic, a sequence of 8192: a KDA layer's
    projections 0.65 TFLOP, the MLA layer 1.16, held experts 0.46 +
    shared 0.12 an expert layer, the dense MLP 1.04, the head 0.77:
    about 8.1 TFLOP; the recurrence as the token form states it 0.03 a
    layer where the issue counted the chunked form's 0.05."""
    cfg = _published()
    s, tera = 8192, 1e12

    def tflop(x):
        return round(x / tera, 2)

    assert tflop(2.0 * s * costs.kda_params(cfg)) == 0.65
    assert tflop(costs.kda_core_flops(cfg, s)) == 0.03
    assert tflop(2.0 * s * costs.mla_params(cfg)
                 + costs.attention_flops(cfg, s)) == 1.16
    assert tflop(costs.grouped_flops(cfg, s)) == 0.46
    assert tflop(2.0 * s * costs.expert_params(cfg)) == 0.12
    assert tflop(costs.ffn_flops(cfg, s, False)) == 1.04
    assert tflop(2.0 * s * 2304 * 20480) == 0.77
    total = costs.sequence_flops(cfg, s)
    assert round(total / tera, 1) == 8.0
    # the head's share near the whole model's own
    assert round(100 * 2.0 * s * 2304 * 20480 / total, 1) == 9.6
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # the recurrence a layer: 8192 x 32 x (4 x 128 x 2 + 129 x 4) B =
    # 403.7 MB, 493 us at the memory's rate against 153 us of products
    assert costs.kda_core_bytes(cfg, s) == 8192 * 32 * 1540 == 403_701_760
    assert round(costs.kda_core_bytes(cfg, s) / 819e9 * 1e6) == 493
    assert round(costs.kda_core_flops(cfg, s) / 197e12 * 1e6) == 153
    assert round(costs.kda_floor_s(cfg, s, peaks) * 1e6) == 1972
    # the MLA layer's attention: compute-bound, 3.49 ms
    assert round(costs.attention_floor_s(cfg, s, peaks) * 1e6) == 3489
    # the grouped product an expert layer: 32,768 pairs x 7,077,888
    # multiply-adds = 0.464 TFLOP, 2.35 ms at the peak; 128 experts'
    # 1.81 GB once and 32,768 rows of 2304 in and out, 2.11 GB, 2.58 ms
    # at the memory's rate: the bytes bind
    assert costs.grouped_flops(cfg, s) == 2.0 * 32_768 * 7_077_888
    assert costs.grouped_bytes(cfg, s) == 2.0 * (
        128 * 7_077_888 + 2 * 32_768 * 2304) == 2_113_929_216
    assert round(costs.grouped_floor_s(cfg, s, peaks) * 1e6) == 4 * 2581
    # under 100 % at any rate the chip could reach
    assert round(total / 197e12 * 1e3, 1) == 40.9


def _hand_trace(kda: bool):
    """Six programs of 100 ns, each: 20 ns under ``block/moe/route``; 50
    under ``block/attn/kda``, of them 10 in the first kernel, 5 in plain
    operations of the recurrence and 15 in the second kernel, all three
    under ``nns_kda_chunk`` (or, ``kda`` false, 50 under
    ``block/attn/mla``); 30 under ``block/mlp``; and one recurrence
    kernel of 40 ns outside any filter program."""
    scope = "jit(nns_filter_m)/block/"
    core = scope + "attn/kda/nns_kda_chunk/"
    modules, ops = [], []
    for i in range(6):
        t = 1000 + 200 * i
        modules.append(["jit_nns_filter_m(17)", t, 100, {}])
        ops.append(["%fusion.3 = f32[8]", t, 20,
                    {"scope": scope + "moe/route/mul:"}])
        if kda:
            ops += [
                ["%fusion.4 = f32[8]", t + 20, 20,
                 {"scope": scope + "attn/kda/dot_general:"}],
                ["%nns_kda_chunk_intra.1 = f32[8] custom-call()", t + 40,
                 10, {"scope": core + "pallas_call:"}],
                ["%fusion.6 = f32[8]", t + 50, 5, {"scope": core + "mul:"}],
                ["%nns_kda_chunk_state.1 = f32[8] custom-call()", t + 55,
                 15, {"scope": core + "pallas_call:"}]]
        else:
            ops.append(["%fusion.4 = f32[8]", t + 20, 50,
                        {"scope": scope + "attn/mla/dot_general:"}])
        ops.append(["%fusion.5 = f32[8]", t + 70, 30,
                    {"scope": scope + "mlp/dot_general:"}])
    modules.append(["jit_other(3)", 2300, 40, {}])
    ops.append(["%nns_kda_chunk_state.9 = f32[8] custom-call()", 2300, 40,
                {}])
    return progtrace.ProgTrace({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.trace_window", 900, 1500, {}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]}]})


@pytest.mark.parametrize("kda", [True, False], ids=["kda", "no_such_scope"])
def test_the_new_readers_on_a_hand_made_trace(kda, monkeypatch):
    """``model_step.kda_device_pct``: the 50 ns of each program under
    ``block/attn/kda`` of the 640 ns of operations. ``kernel.
    nns_kda_chunk.roofline_pct``: six programs' floor over the 180 ns of
    the events named or scoped ``nns_kda_chunk`` inside the filter's
    programs (the kernel outside them counts in no program), not
    capped. ``model_step.mfu.lm_kda``: sequences x ``sequence_flops``
    over the window's seconds x the peak. On a program without the
    scope the first two are None and raise nothing."""
    prog = _hand_trace(kda)
    monkeypatch.setattr(progtrace, "of_run", lambda run: prog)
    cfg = dict(family="kimi_linear", hidden_size=8, kda_num_heads=2,
               kda_head_dim=3, linear_attn_config=dict(
                   kda_layers=[1, 3], full_attn_layers=[2],
                   short_conv_kernel_size=4),
               num_attention_heads=2, kv_lora_rank=4, qk_nope_head_dim=3,
               qk_rope_head_dim=2, v_head_dim=3, intermediate_size=6,
               moe_intermediate_size=5, num_experts=2, num_experts_total=4,
               num_experts_per_token=2, num_shared_experts=1,
               first_k_dense_replace=1, num_hidden_layers=3, vocab_size=10)
    run = {"config": cfg, "sizes": {}, "traffic": {"tokens_per_buffer": 3},
           "peaks": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11},
           "results": {"units_delivered": 7}, "window_s": 2.0}
    share = bench_run.load_reader("model_step.kda_device_pct")(run)
    roofline = bench_run.load_reader("kernel.nns_kda_chunk.roofline_pct")(run)
    if kda:
        assert share == pytest.approx(100 * 300 / 640)
        # two KDA layers x 240 B at 1e11 B/s = 4.8 ns a program
        assert roofline == pytest.approx(100 * 6 * 4.8 / 180)
    else:
        assert share is None and roofline is None
    assert bench_run.load_reader("model_step.mfu.lm_kda")(run) \
        == pytest.approx(100 * 7 * costs.sequence_flops(cfg, 3) / 2e12)
    # a family without the floor, a run without peaks or without a
    # trace: nothing, no raise
    other = dict(run, config=dict(cfg, family="afmoe"))
    assert bench_run.load_reader("kernel.nns_kda_chunk.roofline_pct")(
        other) is None
    assert bench_run.load_reader("kernel.nns_kda_chunk.roofline_pct")(
        dict(run, peaks=None)) is None
    assert bench_run.load_reader("model_step.mfu.lm_kda")(
        dict(run, peaks=None)) is None
    monkeypatch.setattr(progtrace, "of_run", lambda run: None)
    assert bench_run.load_reader("model_step.kda_device_pct")(run) is None
    assert bench_run.load_reader("kernel.nns_kda_chunk.roofline_pct")(
        run) is None


def test_the_cells_kernels_read_their_floors_from_this_family():
    """``kernel.nns_masked_attention.roofline_pct`` and
    ``kernel.nns_grouped_swiglu.roofline_pct`` ask the family's cost
    module for ``attention_floor_s`` / ``grouped_floor_s``: both are
    here under those names."""
    assert callable(costs.attention_floor_s) and callable(
        costs.grouped_floor_s) and callable(costs.kda_floor_s)
