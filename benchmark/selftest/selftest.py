"""``python benchmark/run.py --selftest``: the yardstick's own arithmetic
on fixed samples, on the CPU, in seconds. Percentiles and rates; the
FLOP and byte functions against hand-worked counts for both
configurations; the trace reduction (busy union, idle-gap attribution)
on the small synthetic trace kept beside this file."""
from __future__ import annotations

import json
import math
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def close(a, b, rel=1e-9):
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-12)


def check_stats():
    from nnsbench import stats
    xs = list(range(1, 101))                      # 1..100
    assert close(stats.percentile(xs, 95), 95.05)
    assert close(stats.percentile(xs, 50), 50.5)
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([], 95) is None
    assert close(stats.rate(9000, 30.0), 300.0)
    assert stats.rate(1, 0.0) is None
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5
    assert close(stats.spread([1, 2, 3, 4, 5, 6]), 3.5 / 3.5)


def check_costs():
    from nnsbench import costs
    with open(os.path.join(HERE, "..", "configs", "vit_h14.json")) as f:
        vit = json.load(f)
    with open(os.path.join(HERE, "..", "configs", "dsllm7b_l12.json")) as f:
        gpt = json.load(f)
    # ViT-H/14, worked by hand: 256 tokens; a block's matmul weights
    # 4*1280^2 + 2*1280*5120 = 19,660,800; x 32 = 629,145,600
    assert costs.vit_tokens(vit) == 256
    block_w = 4 * 1280 ** 2 + 2 * 1280 * 5120
    assert block_w == 19_660_800
    # a frame: 2 * 256 * (19,660,800 + 2*256*1280) per block
    per_block = 2 * 256 * (block_w + 2 * 256 * 1280)
    assert per_block == 10_401_873_920
    patch = 2 * 256 * 588 * 1280
    head = 2 * 1280 * 1000
    want = 32 * per_block + patch + head
    assert costs.vit_flops_per_frame(vit) == float(want)
    assert 333e9 < want < 334e9                  # the issue's 333 GFLOP
    # parameters: 632 M (float32: 2.53 GB)
    n = costs.vit_param_count(vit)
    assert 631e6 < n < 633e6, n
    # decoder: a layer 4*4096^2 + 3*4096*11008 = 202,375,168
    assert costs.gpt_layer_params(gpt) == 202_375_168
    n = costs.gpt_param_count(gpt)
    assert n == 12 * (202_375_168 + 8192) + 2 * 102400 * 4096 + 4096
    assert 6.53e9 < 2 * n < 6.55e9               # 6.54 GB in bfloat16
    assert costs.gpt_kv_bytes_per_token(gpt) == 196_608
    # a decoded token at context 400: 2*12*(202,375,168 + 2*400*4096)
    # + 2*4096*102400
    want = 2 * 12 * (202_375_168 + 2 * 400 * 4096) + 2 * 4096 * 102400
    assert costs.gpt_flops_per_token(gpt, 400) == float(want)
    # a decode step at no live context reads layers + head once
    want = (12 * 202_375_168 + 4096 * 102400) * 2
    assert costs.gpt_decode_step_bytes(gpt, 0) == float(want)
    assert costs.gpt_decode_step_bytes(gpt, 1000) == float(
        want + 1000 * 196_608)


def check_trace():
    from nnsbench import traceread
    with open(os.path.join(HERE, "trace_small.json")) as f:
        trace = json.load(f)
    assert traceread.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == \
        [[0, 3], [5, 8]]
    assert traceread.op_kind(
        "%convert_reduce_fusion.32 = (f32[32,256]{1,0:T(8,128)S(1)}) "
        "fusion(bf16[1280]{0} %p.1), kind=kOutput") == "convert_reduce_fusion"
    assert traceread.op_kind("copy.2") == "copy"
    assert traceread.op_kind("jit_call(134)") == "jit_call(134)"
    r = traceread.reduce(trace)
    # window 1000..11000 ns; device busy [1000,3000] U [2500,4000] (union
    # 3000), [6000,7000], [9000,12000] clipped to 11000: 6000 ns busy
    assert close(r["window_s"], 10000e-9), r
    assert close(r["busy_s"], 6000e-9), r
    ops = dict(r["device_ops"])
    assert close(ops["fusion.1"], 3000e-9) and close(ops["copy.2"], 3500e-9)
    gaps = dict(r["idle_gaps"])
    # gap 4000..6000 lies under bench.push (3900..6100); gap 7000..9000 is
    # covered for 600 ns only: unattributed
    assert close(gaps["bench.push"], 2000e-9), gaps
    assert close(gaps["unattributed"], 2000e-9), gaps


def main():
    for fn in (check_stats, check_costs, check_trace):
        fn()
        print(f"selftest {fn.__name__}: ok")
    print("selftest: ok")
    return 0
