"""The ``glm5_ep16_l5.lmstream_s4096`` cell's comparison has been shown
to fail, and its cost arithmetic holds (CPU, the configuration's tiny
``rehearsal`` sizes; ``python -m pytest benchmark/tests -q``):

* a sound run is correct, and the plain reference computed in fp8 (both
  kinds) in the program's place is not, on three seeds;
* a run whose ``logprobs`` or whose ``expert_load`` is altered where it
  is produced comes out ``correct: false``;
* ``nnsbench/costs_glm.py`` against hand-worked counts.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from nnsbench import costs_glm  # noqa: E402

CELL = "glm5_ep16_l5.lmstream_s4096"


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 77])
def test_sound_run_is_correct_and_control_is_not(seed):
    res = bench_run.run_cell(CELL, seed, 1.5, 0, rehearsal=True,
                             control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["info"]["compared"]["buffers_compared"] > 0
    assert res["info"]["counters"]["transfer"]["prepared_leaves"] == 0
    assert not any(res["info"]["control_correct"].values()), (
        "an fp8 control passed the cell's limits", res["info"]["control"])


def _alter_logprobs(apply_fn):
    """Every log-probability handed on one position late."""
    import jax.numpy as jnp

    def broken(params, tokens):
        last, logprobs, load = apply_fn(params, tokens)
        return last, jnp.roll(logprobs, 1), load
    return broken


def _alter_load(apply_fn):
    """Every held expert reports three pairs it did not serve."""
    def broken(params, tokens):
        last, logprobs, load = apply_fn(params, tokens)
        return last, logprobs, load + 3
    return broken


@pytest.mark.parametrize("fault,number", [(_alter_logprobs, "logprob_rms"),
                                          (_alter_load, "load_l1")])
def test_altered_output_is_not_correct(fault, number):
    res = bench_run.run_cell(CELL, 5, 1.5, 0, rehearsal=True, fault=fault)
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
    over = [k for k, n in res["checks"].items() if n["value"] > n["limit"]]
    assert over == [number], res["checks"]


def _published():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "glm5_ep16_l5.json")) as f:
        return json.load(f)


def test_pair_counts_by_hand():
    # positions 0..5, top 4: 1 + 2 + 3 + 4 + 4 + 4 selected, 21 causal
    assert costs_glm.selected_pairs(6, 4) == 18
    assert costs_glm.causal_pairs(6) == 21
    assert costs_glm.selected_pairs(3, 4) == costs_glm.causal_pairs(3) == 6
    # the cell: 2048 x 2049 / 2 + 2048 x 2048
    assert costs_glm.selected_pairs(4096, 2048) == 2_098_176 + 4_194_304
    assert costs_glm.causal_pairs(4096) == 8_390_656


def test_parameter_counts_are_the_issues():
    cfg = _published()
    # q_a 6144x2048, q_b 2048x16384, kv_a 6144x576, kv_b 512x28672,
    # o 16384x6144
    assert costs_glm.attention_params(cfg) == (
        12_582_912 + 33_554_432 + 3_538_944 + 14_680_064 + 100_663_296)
    # wq_b 2048x4096, wk 6144x128, weights_proj 6144x32
    assert costs_glm.indexer_params(cfg) == 8_388_608 + 786_432 + 196_608
    assert costs_glm.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736


def test_sequence_flops_by_hand():
    """A model small enough to count on paper: d 8, 2 heads of 3|1 and
    v 4, ranks 4 and 2, indexer 1 x 2, top 2 of S 3; 2 of 4 experts held,
    1 chosen a token, width 5; dense width 6; vocabulary 10; one dense
    and one expert layer."""
    cfg = dict(hidden_size=8, num_attention_heads=2, q_lora_rank=4,
               kv_lora_rank=2, qk_nope_head_dim=3, qk_rope_head_dim=1,
               v_head_dim=4, index_n_heads=1, index_head_dim=2,
               index_topk=2, intermediate_size=6, moe_intermediate_size=5,
               n_routed_experts=2, n_routed_experts_total=4,
               num_experts_per_tok=1, n_shared_experts=1,
               num_hidden_layers=2, first_k_dense_replace=1, vocab_size=10)
    attn = 8 * 4 + 4 * 2 * 4 + 8 * 3 + 2 * 2 * 7 + 2 * 4 * 8          # 180
    index = 4 * 2 + 8 * 2 + 8 * 1                                     # 32
    assert costs_glm.attention_params(cfg) == attn == 180
    assert costs_glm.indexer_params(cfg) == index == 32
    pairs_sel, pairs_causal = 1 + 2 + 2, 6
    both = 3 * (attn + index) + pairs_causal * 2 + pairs_sel * 2 * (4 + 4)
    dense = 2 * (both + 3 * 3 * 8 * 6)
    # router 8 x 4; shared expert 120; half a routed expert a token
    moe = 2 * (both + 3 * (32 + 120 + 0.5 * 120))
    assert costs_glm.layer_flops(cfg, 3, False) == dense == 2320
    assert costs_glm.layer_flops(cfg, 3, True) == moe == 2728
    assert costs_glm.sequence_flops(cfg, 3) == dense + moe + 2 * 3 * 8 * 10


def test_cell_flops_are_the_issues():
    """ISSUE 28's arithmetic to its rounding: 582 M a token in an expert
    layer, 918 M in the dense one (919.2 counted exactly), 238 M in the
    head slice, 14.2 TFLOP a sequence (14.285)."""
    cfg = _published()
    s = 4096
    assert round(costs_glm.layer_flops(cfg, s, True) / s / 1e6) == 583
    assert round(costs_glm.layer_flops(cfg, s, False) / s / 1e6) == 919
    assert round(2 * cfg["hidden_size"] * cfg["vocab_size"] / 1e6) == 238
    assert round(costs_glm.sequence_flops(cfg, s) / 1e12, 3) == 14.285
