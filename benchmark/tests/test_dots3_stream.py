"""The ``dots3_ep8_l5.lmstream_s8192`` cell's comparison has been shown
to fail, and its arithmetic holds (CPU, the configuration's tiny
``rehearsal`` sizes; ``python -m pytest benchmark/tests -q``):

* a sound run is correct, and the plain reference computed in fp8 (both
  kinds) in the program's place is not, on three seeds;
* a run whose ``logprobs`` or whose ``expert_load`` is altered where it
  is produced comes out ``correct: false``;
* ``nnsbench/costs_dots3_note.py`` against hand-worked counts, a layer
  of each kind, and the published sizes against the configuration's
  reckoning;
* ``attn.window_kept_pct`` against a hand count, and None where there is
  nothing to read.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from nnsbench import costs_dots3_note as costs  # noqa: E402

CELL = "dots3_ep8_l5.lmstream_s8192"
FULL, SLIDING = "full_attention", "sliding_attention"


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 77])
def test_sound_run_is_correct_and_control_is_not(seed):
    res = bench_run.run_cell(CELL, seed, 1.5, 0, rehearsal=True,
                             control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["info"]["compared"]["buffers_compared"] > 0
    report = res["info"]["counters"]["transfer"]
    assert report["prepared_leaves"] == 0
    # five layers, one block of queries each at the rehearsal's 64
    assert report["kernel_calls"] == {"nns_masked_attention": 5}
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
    assert number in over, res["checks"]


def _published():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "dots3_ep8_l5.json")) as f:
        return json.load(f)


def _rehearsal():
    cfg = _published()
    return {**cfg, **cfg["rehearsal"]}


def test_parameter_counts_by_hand_at_the_rehearsal_sizes():
    """d 64; full: 4 heads of 12|4 and v 16, ranks 32 and 16; sliding: 2
    heads of 20|4 and v 16, ranks 32 and 32; indexer 2 x 8."""
    cfg = _rehearsal()
    # q_a, q_b, kv_a, kv_b, o, the headwise gate
    full = 64 * 32 + 32 * 4 * 16 + 64 * 20 + 16 * 4 * 28 + 4 * 16 * 64 \
        + 64 * 4
    window = 64 * 32 + 32 * 2 * 24 + 64 * 36 + 32 * 2 * 36 + 2 * 16 * 64 \
        + 64 * 2
    assert costs.attention_params(cfg, FULL) == full == 11520
    assert costs.attention_params(cfg, SLIDING) == window == 10368
    assert costs.indexer_params(cfg) == 32 * 16 + 64 * 8 + 64 * 2 == 1152
    assert costs.expert_params(cfg) == 3 * 64 * 32


def test_sequence_flops_by_hand_at_the_rehearsal_sizes():
    """64 tokens: a full layer keeps sum_t min(t + 1, 16) = 136 + 48 x 16
    = 904 pairs and its indexer scores 2080 causal ones; a sliding layer
    keeps sum_t min(t + 1, 17) = 153 + 47 x 17 = 952. Of a token's 4
    chosen experts of 32, 8 held: 1 on average."""
    cfg, s = _rehearsal(), 64
    assert costs.kept_pairs(cfg, s, FULL) == 904
    assert costs.kept_pairs(cfg, s, SLIDING) == 952
    attn_full = 2 * 904 * 4 * (12 + 4 + 16)
    attn_window = 2 * 952 * 2 * (20 + 4 + 16)
    assert costs.attention_flops(cfg, s, FULL) == attn_full
    assert costs.attention_flops(cfg, s, SLIDING) == attn_window
    index = 2080 * 2 * 8
    moe = 64 * 32 + (1 + 1) * 3 * 64 * 32        # router, shared + 1
    dense = 3 * 64 * 128
    layers = [2 * (s * (11520 + 1152 + dense) + index) + attn_full,
              2 * (s * (11520 + 1152 + moe) + index) + attn_full] \
        + [2 * s * (10368 + moe) + attn_window] * 3
    assert [costs.layer_flops(cfg, s, kind, i >= 1) for i, kind in
            enumerate(cfg["layer_types"])] == layers
    assert costs.sequence_flops(cfg, s) == sum(layers) + 2 * s * 64 * 64 \
        == 19_288_320


def test_attention_floor_by_hand_at_the_rehearsal_sizes():
    """Each layer the larger of its operations and its bytes (q, k, v,
    o once a head, two bytes each), over the rates given."""
    cfg, s = _rehearsal(), 64
    flops_full, flops_window = 2 * 904 * 4 * 32, 2 * 952 * 2 * 40
    bytes_full, bytes_window = 2 * s * 4 * 2 * (16 + 16), \
        2 * s * 2 * 2 * (24 + 16)
    assert costs.attention_bytes(cfg, s, FULL) == bytes_full
    assert costs.attention_bytes(cfg, s, SLIDING) == bytes_window
    both = {"flops_bf16": 1.0, "hbm_bytes_per_s": 1.0}
    assert costs.attention_floor_s(cfg, s, both) \
        == 2 * flops_full + 3 * flops_window
    slow = {"flops_bf16": 1.0, "hbm_bytes_per_s": 1e-3}
    assert costs.attention_floor_s(cfg, s, slow) == pytest.approx(
        1e3 * (2 * bytes_full + 3 * bytes_window))


def test_parameter_counts_are_the_reckoned_ones():
    """The configuration file's ``reckoned_bytes``: a full sublayer
    144.05 M (MLA 134.02 M, gate 0.66 M, indexer 9.37 M), a sliding one
    90.83 M, an expert layer 779.88 M, the dense MLP 212.34 M, the slice
    2 x 97.32 M; 4.087 B in all."""
    cfg = _published()
    d = cfg["hidden_size"]
    full = costs.attention_params(cfg, FULL)
    assert round((full + costs.indexer_params(cfg)) / 1e6, 2) == 144.05
    assert round(costs.attention_params(cfg, SLIDING) / 1e6, 2) == 90.83
    experts = d * 256 + (1 + 32) * costs.expert_params(cfg)
    assert round(experts / 1e6, 2) == 779.88
    dense = 3 * d * cfg["intermediate_size"]
    assert round(dense / 1e6, 2) == 212.34
    total = 2 * (full + costs.indexer_params(cfg)) + dense + 4 * experts \
        + 3 * costs.attention_params(cfg, SLIDING) \
        + 2 * d * cfg["vocab_size"]
    assert round(total / 1e9, 3) == 4.087


def test_cell_flops_are_the_reckoned_ones():
    """About 21.5 TFLOP a sequence of 8192 tokens."""
    assert round(costs.sequence_flops(_published(), 8192) / 1e12, 1) \
        == 21.5


def _window_run(window, tokens):
    config = {} if window is None else {"sliding_window_size": window}
    return {"config": config, "sizes": {},
            "traffic": {"tokens_per_buffer": tokens}}


@pytest.mark.parametrize("tokens,tiles", [(1536, 5), (8192, 31)])
def test_window_kept_share_by_hand(tokens, tiles):
    """513 keys over tiles of 512: query tile i walks key tiles i - 1
    and i (tile 0 its own alone), 512 x 512 pairs each; the window keeps
    513 x 514 / 2 + (S - 513) x 513 of them."""
    read = bench_run.load_reader("attn.window_kept_pct")
    kept = 513 * 514 // 2 + (tokens - 513) * 513
    assert read(_window_run(513, tokens)) == pytest.approx(
        100.0 * kept / (tiles * 512 * 512))


def test_window_kept_share_reads_nothing_where_nothing_is(monkeypatch):
    from nnstreamer_tpu.ops import sparse_attention
    read = bench_run.load_reader("attn.window_kept_pct")
    assert read(_window_run(None, 8192)) is None
    monkeypatch.delattr(sparse_attention, "pairs_walked")
    assert read(_window_run(513, 8192)) is None


def test_the_new_metrics_list_the_cell_alone():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in ("attn.window_kept_pct", "model_step.mfu.lm_dots3_note"):
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "frames_per_s"
