"""The ``brumby_14b_pp4_l10.lmdoc_s4096x8`` cell's comparison has been
shown to fail, its cost arithmetic holds and its readers read (CPU, the
configuration's tiny ``rehearsal`` sizes; ``python -m pytest
benchmark/tests -q``):

* a sound run is correct (documents of 8 buffers on one stream, the
  state held by the filter), and the plain reference computed in fp8
  (both kinds) in the program's place is not, on three seeds;
* a run whose state is reset at every buffer, whose gate is dropped
  (``g = 0``), whose normaliser is dropped, or whose last row or
  ``logprobs`` are rolled comes out ``correct: false``;
* ``nnsbench/costs_brumby.py`` against hand-worked counts and the
  issue's;
* the three new readers on a trace made by hand, and None on a program
  without the scope.

The planted faults are also what ``.scratch`` scripts import to plant
them at the cell's own size on the chip (PERF.md section 2).
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench_run  # noqa: E402
from nnsbench import costs_brumby as costs, progtrace  # noqa: E402

CELL = "brumby_14b_pp4_l10.lmdoc_s4096x8"


@pytest.mark.parametrize("seed", [11, 2_500_000_011, 77])
def test_sound_run_is_correct_and_control_is_not(seed):
    res = bench_run.run_cell(CELL, seed, 2.0, 0, rehearsal=True,
                             control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    compared = res["info"]["compared"]
    # whole passes of one document, 8 buffers each
    assert compared["passes_compared"] >= 1
    assert compared["buffers_compared"] == 8 * compared["passes_compared"]
    report = res["info"]["counters"]["transfer"]
    assert report["kernel_calls"] == {"nns_power_retention": 3}
    # three layers' (S, Z) of 2 heads: 144 x 16 and 16 x 16 float32
    assert report["state"]["leaves"] == 6 and report["state"]["drops"] == 0
    assert report["state"]["bytes"] == 3 * 2 * (144 * 16 + 16 * 16) * 4
    assert report["state"]["dispatches"] >= res["attempted"]
    assert not any(res["info"]["control_correct"].values()), (
        "an fp8 control passed the cell's limits", res["info"]["control"])


def _with_layers(params, change):
    return dict(params, layers=[change(layer) for layer in params["layers"]])


def reset_the_state_every_buffer(apply_fn):
    """Every buffer meets an empty state, as a filter that forgot it, or
    a model that reset it at every ``position0``, would give it."""
    import jax

    def broken(params, state, tokens, position0):
        return apply_fn(params, jax.tree.map(lambda x: x * 0, state),
                        tokens, position0)
    return broken


def drop_the_gate(apply_fn):
    """``g = 0``: nothing is ever forgotten (``W_g`` zero and an offset
    far above zero, where logsigmoid gives 0)."""
    def no_gate(layer):
        a = layer["attn"]
        return dict(layer, attn=dict(a, wg=a["wg"] * 0,
                                     bg=a["bg"] * 0 + 1e30))

    def broken(params, state, tokens, position0):
        return apply_fn(_with_layers(params, no_gate), state, tokens,
                        position0)
    return broken


def drop_the_normaliser(apply_fn):
    """``o = sum_s a_ts v_s``, not divided by the weights' sum: the
    op's ``eps`` raised to ``2^20`` (far above any sum of weights, so
    the quotient is the numerator over a constant) and the output
    projection multiplied by the same power of two (exact in
    bfloat16). The op's programs traced under the raised ``eps`` are
    dropped again afterwards."""
    from nnstreamer_tpu.ops import power_retention as op
    big = float(2 ** 20)

    def unnormalised(layer):
        a = layer["attn"]
        return dict(layer, attn=dict(a, wo=a["wo"] * big))

    def broken(params, state, tokens, position0):
        eps, op.EPS = op.EPS, big
        op._call.clear_cache()
        try:
            return apply_fn(_with_layers(params, unnormalised), state,
                            tokens, position0)
        finally:
            op.EPS = eps
            op._call.clear_cache()
    return broken


def _roll_last_row(apply_fn):
    """The last position's logits handed on one class late."""
    import jax.numpy as jnp

    def broken(params, state, tokens, position0):
        (last, logprobs), state = apply_fn(params, state, tokens, position0)
        return (jnp.roll(last, 1), logprobs), state
    return broken


def _roll_logprobs(apply_fn):
    """Every log-probability handed on one position late."""
    import jax.numpy as jnp

    def broken(params, state, tokens, position0):
        (last, logprobs), state = apply_fn(params, state, tokens, position0)
        return (last, jnp.roll(logprobs, 1)), state
    return broken


@pytest.mark.parametrize("fault,numbers", [
    (reset_the_state_every_buffer, ["logprob_rms"]),
    (drop_the_gate, ["logprob_rms"]),
    (drop_the_normaliser, ["logprob_rms"]),
    (_roll_last_row, ["logit_rms", "logit_gap"]),
    (_roll_logprobs, ["logprob_rms"])],
    ids=["state_reset", "gate_dropped", "normaliser_dropped", "row_rolled",
         "logprobs_rolled"])
def test_altered_output_is_not_correct(fault, numbers):
    res = bench_run.run_cell(CELL, 5, 2.0, 0, rehearsal=True, fault=fault)
    assert res["attempted"] > 0
    assert not res["correct"], res["checks"]
    over = [k for k, n in res["checks"].items() if n["value"] > n["limit"]]
    assert set(numbers) <= set(over), res["checks"]


def _published():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "brumby_14b_pp4_l10.json")) as f:
        return json.load(f)


def test_configuration_file_states_its_cut():
    """Every number of the catalog's ``config`` under the same key, the
    two cuts listed, every assumed item with its sentence."""
    cfg = _published()
    catalog = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differ = sorted(k for k, v in catalog.items() if cfg[k] != v)
    assert differ == sorted(cfg["reduced"]) == ["num_hidden_layers",
                                                "vocab_size"]
    assert cfg["published"] == {k: catalog[k] for k in cfg["reduced"]}
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert (cfg["pipeline_stages"], cfg["vocab_parallel"]) == (4, 4)
    assert cfg["num_hidden_layers"] * cfg["pipeline_stages"] == 40
    assert cfg["vocab_size"] * cfg["vocab_parallel"] == 151936
    for item in ("retention_degree", "retention_gate", "retention_heads",
                 "retention_normaliser", "qk_norm_rope", "sources"):
        assert "could not be opened from the sandbox" in cfg["assumed"][item]
    assert "3,692,490,240 parameters" in cfg["reckoned_bytes"]
    assert "7.385 GB" in cfg["reckoned_bytes"]


def test_parameter_counts_are_the_files():
    cfg = _published()
    # wq and wo 5120 x 5120, wk and wv 5120 x 1024, the gate 5120 x 8,
    # the MLP's three 5120 x 17408
    assert costs.layer_params(cfg) == (
        2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 3 * 5120 * 17408
    ) == 330_342_400
    # the file's count adds the norms' vectors: 2 x 5120 + 2 x 128
    layer = 330_342_400 + 2 * 5120 + 2 * 128
    assert layer == 330_352_896
    total = 10 * layer + 2 * 37_984 * 5120 + 5120
    assert total == 3_692_490_240
    assert round(total * 2 / 1e9, 3) == 7.385
    assert round(100 * total * 2 / 16e9) == 46
    assert costs.phi_rows(cfg) == 8256


def test_buffer_flops_by_hand():
    """A model small enough to count on paper: d 8; 4 query heads on 2
    key/value heads of 4 (phi: 10 rows); MLP 6 wide; 2 layers;
    vocabulary 10; a buffer of 3 tokens."""
    cfg = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
               head_dim=4, intermediate_size=6, num_hidden_layers=2,
               vocab_size=10)
    assert costs.phi_rows(cfg) == 10
    # q and o 8 x 16 each, k and v 8 x 8 each, the gate 8 x 2, the
    # MLP's three 8 x 6
    layer = 2 * 128 + 2 * 64 + 16 + 3 * 48
    assert costs.layer_params(cfg) == layer == 544
    # a token reads the 10 x 4 state for each of 4 query heads and
    # updates it for each of 2 key/value heads, 2 operations an entry
    assert costs.retention_flops(cfg, 3) == 3 * 2 * 10 * 4 * (4 + 2) == 1440
    # q and o 3 x 4 x 4 each and k and v 3 x 2 x 4 each in bfloat16,
    # the log-gates 3 x 2 in float32, 2 states of 10 x 4 + 10 in float32
    # read and written
    assert costs.retention_bytes(cfg, 3) == (
        2 * (2 * 48 + 2 * 24) + 4 * 6 + 2 * 4 * 2 * 50) == 1112
    assert costs.buffer_flops(cfg, 3) == (
        2 * (2 * 3 * layer + 1440) + 2 * 3 * 8 * 10) == 9888
    peaks = {"flops_bf16": 10.0, "hbm_bytes_per_s": 1000.0}
    # two layers: the operations bind at these toy peaks, the bytes
    # where the memory is slow
    assert costs.retention_floor_s(cfg, 3, peaks) == 2 * 144.0
    assert costs.retention_floor_s(
        cfg, 3, dict(peaks, hbm_bytes_per_s=1.0)) == 2 * 1112.0


def test_cell_flops_are_the_issues():
    """ISSUE 40's arithmetic, a token and layer: 661 MFLOP of dense
    products; the state read 84.5 and updated 16.9 (the issue adds 10.5
    inside chunks of 1024, which the token-by-token form has not); a
    buffer of 4096: 27.1 TFLOP of layers' matrices, 4.16 of retention,
    1.59 of head."""
    cfg = _published()
    s = 4096

    def mflop(x):
        return round(x / 1e6, 1)

    assert mflop(2.0 * costs.layer_params(cfg)) == 660.7
    a_token = costs.retention_flops(cfg, 1)
    assert mflop(a_token) == 101.4
    assert mflop(2.0 * 8256 * 128 * 40) == 84.5
    assert mflop(2.0 * 8256 * 128 * 8) == 16.9
    total = costs.buffer_flops(cfg, s)
    assert round(10 * 2.0 * s * costs.layer_params(cfg) / 1e12, 1) == 27.1
    assert round(10 * costs.retention_flops(cfg, s) / 1e12, 2) == 4.16
    head = 2.0 * s * 5120 * 37984
    assert round(head / 1e12, 2) == 1.59
    assert round(total / 1e12, 1) == 32.8
    # the head's share near the whole model's own, the retention's the
    # architecture's constant
    assert round(100 * head / total, 1) == 4.9
    assert round(100 * 10 * costs.retention_flops(cfg, s) / total, 1) == 12.7
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    # a layer's retention: 415.4 GFLOP = 2.109 ms at the peak; q, o 41.9
    # MB each, k, v 8.4 MB each, the gates 0.13, the state 67.9 MB read
    # and written = 168.7 MB = 0.206 ms at the memory's rate: the
    # operations bind
    assert round(costs.retention_flops(cfg, s) / 197e12 * 1e6) == 2109
    assert costs.retention_bytes(cfg, s) == (
        4096 * (2 * 128 * 96 + 4 * 8) + 2 * 4 * 8 * 8256 * 129)
    assert round(costs.retention_bytes(cfg, s) / 819e9 * 1e6) == 206
    assert round(costs.retention_floor_s(cfg, s, peaks) * 1e6) == 21093
    # a kernel that multiplied a padded 16384-row square would read
    # under 50 % of the floor at the peak rate, one on 8320 rows 99.2
    assert round(100 * 8256 / 16384, 1) == 50.4
    assert round(100 * 8256 / 8320, 1) == 99.2
    # under 100 % at any rate the chip could reach
    assert round(total / 197e12 * 1e3, 1) == 166.5


def _hand_trace(retention: bool):
    """Six programs of 100 ns, each: 10 ns under ``embed``; 50 under
    ``block/attn/retention``, of them 5 in a plain operation of the op
    (the gates' running sum) and 15 in the kernel, both under
    ``nns_power_retention`` (or, ``retention`` false, 50 under
    ``block/attn/full``); 40 under ``block/mlp``; and one kernel of 40
    ns outside any filter program."""
    scope = "jit(nns_filter_m)/block/"
    core = scope + "attn/retention/nns_power_retention/"
    modules, ops = [], []
    for i in range(6):
        t = 1000 + 200 * i
        modules.append(["jit_nns_filter_m(17)", t, 100, {}])
        ops.append(["%fusion.3 = f32[8]", t, 10,
                    {"scope": "jit(nns_filter_m)/embed/gather:"}])
        if retention:
            ops += [
                ["%fusion.4 = f32[8]", t + 10, 30,
                 {"scope": scope + "attn/retention/dot_general:"}],
                ["%fusion.6 = f32[8]", t + 40, 5,
                 {"scope": core + "cumsum:"}],
                ["%nns_power_retention.1 = f32[8] custom-call()", t + 45,
                 15, {"scope": core + "pallas_call:"}]]
        else:
            ops.append(["%fusion.4 = f32[8]", t + 10, 50,
                        {"scope": scope + "attn/full/dot_general:"}])
        ops.append(["%fusion.5 = f32[8]", t + 60, 40,
                    {"scope": scope + "mlp/dot_general:"}])
    modules.append(["jit_other(3)", 2300, 40, {}])
    ops.append(["%nns_power_retention.9 = f32[8] custom-call()", 2300, 40,
                {}])
    return progtrace.ProgTrace({"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["bench.trace_window", 900, 1500, {}]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]}]})


@pytest.mark.parametrize("retention", [True, False],
                         ids=["retention", "no_such_scope"])
def test_the_new_readers_on_a_hand_made_trace(retention, monkeypatch):
    """``model_step.retention_device_pct``: the 20 ns of each program
    under ``nns_power_retention`` of the 640 ns of operations (the
    kernel outside the programs counts among the operations).
    ``kernel.nns_power_retention.roofline_pct``: six programs' floor
    over the 120 ns of the events named or scoped so inside the
    filter's programs, not capped. ``model_step.mfu.lm_retention``:
    buffers x ``buffer_flops`` over the window's seconds x the peak. On
    a program without the scope the first two are None and raise
    nothing."""
    prog = _hand_trace(retention)
    monkeypatch.setattr(progtrace, "of_run", lambda run: prog)
    sizes = dict(hidden_size=8, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=4, intermediate_size=6,
                 num_hidden_layers=2, vocab_size=10)
    run = {"config": {"family": "brumby"}, "sizes": sizes,
           "traffic": {"tokens_per_buffer": 3},
           "peaks": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11},
           "results": {"units_delivered": 7}, "window_s": 2.0}
    share = bench_run.load_reader("model_step.retention_device_pct")(run)
    roofline = bench_run.load_reader(
        "kernel.nns_power_retention.roofline_pct")(run)
    if retention:
        assert share == pytest.approx(100 * 120 / 640)
        # two layers x 1112 B at 1e11 B/s = 22.24 ns a program (1440
        # operations at 1e12 a second are 1.44 ns: the bytes bind here)
        assert roofline == pytest.approx(100 * 6 * 22.24 / 120)
    else:
        assert share is None and roofline is None
    assert bench_run.load_reader("model_step.mfu.lm_retention")(run) \
        == pytest.approx(100 * 7 * 9888 / 2e12)
    # a family without the floor, a run without peaks or without a
    # trace: nothing, no raise
    reader = bench_run.load_reader("kernel.nns_power_retention.roofline_pct")
    assert reader(dict(run, config={"family": "afmoe"})) is None
    assert reader(dict(run, peaks=None)) is None
    assert bench_run.load_reader("model_step.mfu.lm_retention")(
        dict(run, peaks=None)) is None
    monkeypatch.setattr(progtrace, "of_run", lambda run: None)
    assert bench_run.load_reader("model_step.retention_device_pct")(
        run) is None
    assert reader(run) is None


def test_the_benchmark_lists_the_cell_and_its_readers():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, = [c for c in bench["workloads"] if c["name"] == CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "brumby_14b_pp4_l10", "lmdoc_s4096x8")
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
    config = bench["configs"][-1]
    assert config["name"] == "brumby_14b_pp4_l10"
    assert config["reduced"] == _published()["reduced"]
    reported = [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", [])]
    assert reported[-3:] == ["model_step.mfu.lm_retention",
                             "model_step.retention_device_pct",
                             "kernel.nns_power_retention.roofline_pct"]
    assert len(reported) == 20
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL] and m["moves"] == "frames_per_s"
        assert callable(bench_run.load_reader(m["name"]))
    for name in ("frames_per_s", "latency_p95_ms"):
        m, = [m for m in bench["end_to_end"] if m["name"] == name]
        assert m["workloads"][-1] == CELL
