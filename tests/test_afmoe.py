"""``models/afmoe.py`` (window and full grouped-query layers in one
stack, a gate on the attention's output, norms on both sides of each
sublayer, a sigmoid router over the experts held) at a tiny size on the
CPU with seeded weights, against the benchmark's plain reference
(``benchmark/refs/afmoe.py``, which imports nothing of the program) and
against hand-worked values."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.models import afmoe, glm_dsa, latent, longcat, zoo
from nnstreamer_tpu.ops import sparse_attention

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from refs import afmoe as ref  # noqa: E402

SLIDING, FULL = afmoe.SLIDING, afmoe.FULL
# the configuration's rehearsal sizes (benchmark/configs/
# trinity_mini_pp8_l5.json) and its five layers: a window of 16 is live
# at 64 tokens, 4 heads read 2 key/value heads, 16 experts choosing 4
SIZES = dict(
    vocab_size=64, hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=128, moe_intermediate_size=32, num_experts=16,
    num_experts_per_tok=4, num_shared_experts=1, route_norm=True,
    route_scale=2.826, score_func="sigmoid", sliding_window=16,
    layer_types=[SLIDING, SLIDING, FULL, SLIDING, SLIDING],
    mup_enabled=True, rms_norm_eps=1e-5, rope_theta=10000.0)
SEQ = 64
# the reference's view of the share below: experts 4..7 of 16
RANK1 = dict(SIZES, expert_rank=1)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several attention blocks and several expert tiles at this size."""
    monkeypatch.setattr(afmoe, "BLOCK_Q", 16)
    monkeypatch.setattr(latent, "EXPERT_TILE", 8)


def _cfg(dtype=jnp.float32, **over):
    share = dict(held_first=4, held_count=4, dtype=dtype)
    share.update(over)
    return afmoe.AfmoeConfig.from_hf(SIZES, **share)


def _tokens(seed, n=SEQ):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n,
                                                np.int32)


def _run(cfg, params, tokens):
    out = jax.jit(lambda p, t: afmoe.forward(p, t[None], cfg))(params,
                                                               tokens)
    return np.asarray(out[0][0]), np.asarray(out[1][0]), np.asarray(out[2])


def _hidden(seed, cfg, rows=SEQ):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (rows, cfg.hidden_size), jnp.float32)


# float32: the two sides differ in the order of their sums (measured
# 4e-7 of the logits' range, 2.4e-6 in a log-probability over three
# seeds). bfloat16: an activation carries 8 bits and a moved expert a
# quarter of a token's routed weight; measured 0.005-0.009 of the
# logits' range, 0.04-0.66 in a log-probability (one token of one seed
# routed otherwise) and 1-6 of the ~250 pairs a load counts: the
# tolerances stand 3x over
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,logit_tol,logprob_tol,load_tol", [
    (jnp.float32, 1e-5, 3e-5, 0), (jnp.bfloat16, 0.03, 2.0, 18)],
    ids=["float32", "bfloat16"])
def test_program_against_plain_reference(seed, dtype, logit_tol,
                                         logprob_tol, load_tol):
    cfg = _cfg(dtype)
    params = afmoe.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _tokens(seed + 10)
    last, logprobs, load = _run(cfg, params, tokens)
    want = ref.forward(params, tokens, RANK1, "f32")
    assert load.shape == want[2].shape == (4, 4)
    assert np.abs(last - want[0]).max() \
        <= logit_tol * (want[0].max() - want[0].min())
    assert np.abs(logprobs - want[1]).max() <= logprob_tol
    assert logprobs[-1] == 0 and (logprobs[:-1] < 0).all()
    assert np.abs(load - want[2]).sum() <= load_tol
    assert (want[2].sum(-1) > 0).all()


@pytest.mark.parametrize("without", ["gated", "qk_norm", "post_norm"])
def test_each_mechanism_is_in_the_result(without):
    """The gate on the attention's output, the q/k norms and the norms
    after the sublayers: the reference with one of them removed is far
    from the program, which holds all three (it agrees with the whole
    reference to 3e-5, the test above)."""
    cfg = _cfg()
    params = afmoe.init_params(cfg, jax.random.PRNGKey(3))
    # q and k norms of ones do nothing to a head whose rms is already 1
    params = jax.tree.map(lambda w: w * 1.5 if w.ndim == 1 else w, params)
    tokens = _tokens(13)
    _, logprobs, _ = _run(cfg, params, tokens)
    whole = ref.forward(params, tokens, RANK1, "f32")
    lacking = ref.forward(params, tokens, RANK1, "f32", **{without: False})
    assert np.abs(logprobs - whole[1]).max() <= 3e-5
    assert np.abs(logprobs - lacking[1]).max() > 1e-2


def test_a_window_layer_keeps_its_window_and_a_full_layer_no_position():
    """One layer's attention half, both kinds, on the same weights. The
    window layer differs from itself without its window (a window
    longer than the sequence) from the first query that loses a key on,
    and not before. The full layer encodes no position: its last row is
    the same whatever order the earlier rows come in, which a rotated
    layer's is not."""
    cfg, no_window = _cfg(), _cfg(sliding_window=SEQ)
    layer = afmoe.init_params(cfg, jax.random.PRNGKey(5))["layers"][1]
    h = _hidden(6, cfg)
    win = afmoe.attend(h, layer, SLIDING, cfg)
    wide = afmoe.attend(h, layer, SLIDING, no_window)
    np.testing.assert_allclose(win[:16], wide[:16], atol=1e-6)
    assert float(jnp.abs(win[16:] - wide[16:]).max()) > 1e-2
    order = np.concatenate([np.random.default_rng(0).permutation(SEQ - 1),
                            [SEQ - 1]])
    full = afmoe.attend(h, layer, FULL, cfg)
    np.testing.assert_allclose(afmoe.attend(h[order], layer, FULL, cfg)[-1],
                               full[-1], atol=2e-6)
    rotated = afmoe.attend(h[order], layer, SLIDING, no_window)
    assert float(jnp.abs(rotated[-1] - wide[-1]).max()) > 1e-2
    # a full layer differs from the window layer on the same weights
    assert float(jnp.abs(full - win).max()) > 1e-2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up(seed):
    """The routed parts of all four shares of 4 of the 16 experts (the
    tile loops) plus the shared expert once are the uncut layer's output
    (the kernel's path), and the shares' loads side by side are the
    uncut layer's load: every token-expert pair is served by exactly one
    share."""
    full = _cfg(held_first=0, held_count=0)
    m = afmoe.init_params(full, jax.random.PRNGKey(seed))["layers"][2]["moe"]
    x = _hidden(seed + 20, full)
    choice, weight = latent.sigmoid_route(x, m, full.num_experts_per_tok,
                                          full.route_scale)
    whole, load = latent.routed_experts(x, m, choice, weight, 0)
    shared = latent.swiglu(x, m["shared"])
    total, loads = shared, []
    for rank in range(4):
        part = dict(m, experts=jax.tree.map(
            lambda w: w[4 * rank:4 * rank + 4], m["experts"]))
        out, load_r = latent.routed_experts(x, part, choice, weight,
                                            4 * rank)
        total = total + (out - shared)
        loads.append(np.asarray(load_r))
    np.testing.assert_allclose(total, whole, atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(loads), load)
    assert int(load.sum()) == SEQ * full.num_experts_per_tok


def test_the_decoders_share_their_parts():
    """One router, one SwiGLU, one attention half with its output
    projection, one expert layer, one scoring head: the three decoders
    name the same function objects, and none the grouped product's but
    through the expert layer."""
    assert afmoe.sigmoid_route is latent.sigmoid_route
    assert afmoe.routed_experts is longcat.routed_experts \
        is latent.routed_experts
    assert afmoe.causal_attention_out is latent.causal_attention_out
    assert afmoe.rmsnorm is longcat.rmsnorm is latent.rmsnorm
    # the latent decoders' attention half is latent.mla_attention
    for module in (glm_dsa, longcat):
        assert not hasattr(module, "causal_attention_out"), module
    assert afmoe.swiglu is longcat.swiglu is latent.swiglu
    for module in (afmoe, glm_dsa, longcat):
        assert not hasattr(module, "grouped_swiglu") \
            and not hasattr(module, "group_by_expert"), module
    assert not hasattr(glm_dsa, "route")
    assert latent.blocked_causal_attention \
        is sparse_attention.blocked_causal_attention


def test_config_reads_the_published_keys():
    hf = dict(SIZES, model_type="afmoe", max_position_embeddings=131072,
              rope_scaling=None, n_group=1, topk_group=1,
              global_attn_every_n_layers=4, use_grouped_mm=True)
    cfg = afmoe.AfmoeConfig.from_hf(hf, held_first=8, held_count=4)
    assert (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.n_moe_layers,
            cfg.num_key_value_heads, cfg.sliding_window) == (5, 1, 4, 2, 16)
    assert cfg.kinds == (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    assert cfg.held == 4 and afmoe.AfmoeConfig.from_hf(hf).held == 16
    # without layer_types every global_attn_every_n_layers-th is full
    plain = afmoe.AfmoeConfig.from_hf(
        {k: v for k, v in hf.items() if k != "layer_types"},
        num_hidden_layers=8)
    assert plain.kinds == (SLIDING,) * 3 + (FULL,) + (SLIDING,) * 3 + (FULL,)
    with pytest.raises(ValueError, match="outside"):
        afmoe.AfmoeConfig.from_hf(hf, held_first=14, held_count=4)
    with pytest.raises(ValueError, match="layer_types"):
        afmoe.AfmoeConfig.from_hf(dict(hf, layer_types=[SLIDING, "chunked"]))
    with pytest.raises(ValueError, match="sigmoid"):
        afmoe.AfmoeConfig.from_hf(dict(hf, score_func="softmax"))
    with pytest.raises(ValueError, match="divide"):
        afmoe.AfmoeConfig.from_hf(dict(hf, num_key_value_heads=3))
    with pytest.raises(ValueError, match="unknown option"):
        zoo.build("afmoe", hidden="64")


@pytest.mark.parametrize("share,grouped", [("", 3)], ids=["default"])
def test_whole_router_runs_the_grouped_kernel(share, grouped):
    """``kernel_calls`` through the pipeline: ``nns_grouped_swiglu`` once
    an expert layer where the whole router is held (the other shares,
    on both sides of half the router, read off the traced program:
    ``test_grouped.py::test_the_share_picks_the_form``)."""
    caps = ("other/tensors,format=static,num_tensors=1,types=(string)int32,"
            "dimensions=(string)64,framerate=0/1")
    p = parse_launch(f'appsrc name=in caps="{caps}" ! tensor_filter name=f '
                     f'framework=jax model=zoo://afmoe?seq=64&seed=3{share} '
                     f'! appsink name=out')
    p.start()
    p["in"].push_buffer(Buffer.from_arrays([_tokens(0)]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    report = p["f"].transfer_report()
    p.stop()
    # 4 layers x 4 blocks of 16 queries; 3 of the 4 are expert layers
    assert report["kernel_calls"] == {"nns_masked_attention": 16,
                                      "nns_grouped_swiglu": grouped}


@pytest.mark.parametrize("window", ["", "in-flight=4 prefetch-host=true"],
                         ids=["window1", "window4"])
def test_pipeline_gives_the_direct_calls_three_tensors(window):
    uri = "zoo://afmoe?seq=64&held_first=4&held_count=4&seed=3"
    apply_fn, params, in_info, out_info = zoo.build(
        "afmoe", seq="64", held_first="4", held_count="4", seed="3")
    assert [tuple(i.shape) for i in out_info] == [(64,), (64,), (3, 4)]
    frames = [_tokens(i) for i in range(5)]
    want = [jax.jit(apply_fn)(params, f) for f in frames]
    caps = ("other/tensors,format=static,num_tensors=1,types=(string)int32,"
            "dimensions=(string)64,framerate=0/1")
    p = parse_launch(f'appsrc name=in caps="{caps}" ! tensor_filter name=f '
                     f'framework=jax model={uri} {window} ! appsink name=out')
    p.start()
    for f in frames:
        p["in"].push_buffer(Buffer.from_arrays([f]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    got = [[np.asarray(c.host()) for c in b.chunks] for b in p["out"].buffers]
    report = p["f"].transfer_report()
    p.stop()
    # 4 layers (three window, one full) x 4 blocks of 16 queries; 4 of
    # 16 experts held: the split form's two kernels twice in each of the
    # 3 expert layers (ops/grouped.py::takes_split)
    assert report["kernel_calls"] == {"nns_masked_attention": 16,
                                      "nns_grouped_gate_up": 6,
                                      "nns_grouped_down": 6}
    assert report.get("prepared_leaves", 0) == 0
    # a layer's four projections reshaped and transposed a head at a
    # time, and a router bias's conversion an expert layer
    assert report["prepared_equations"] == 4 * 8 + 3
    assert len(got) == 5
    for g, w in zip(got, want):
        assert [x.dtype for x in g] == [np.float32, np.float32, np.int32]
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, np.asarray(b))
