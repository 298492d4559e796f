"""``models/kimi_linear.py`` (gated delta-rule layers three to one
position-free latent layer, a dense first layer, a sigmoid router over
the experts held) at a tiny size on the CPU with seeded weights, against
the benchmark's plain reference (``benchmark/refs/kimi_linear.py``,
which imports nothing of the program and runs the recurrence token by
token) and against hand-worked values."""
import dataclasses
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.models import afmoe, glm_dsa, kimi_linear, latent, zoo

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from refs import glm_dsa as ref_parts  # noqa: E402
from refs import kimi_linear as ref  # noqa: E402

KDA, MLA = kimi_linear.KDA, kimi_linear.MLA
# the configuration's rehearsal sizes (benchmark/configs/
# kimi_linear_ep2_l5.json) and its five layers as config.json spells
# them: 64 tokens are four chunks of 16, 4 heads of 16, 16 experts
# choosing 4
HF = dict(
    model_type="kimi_linear", vocab_size=64, hidden_size=64,
    num_hidden_layers=5, first_k_dense_replace=1, intermediate_size=128,
    linear_attn_config=dict(kda_layers=[1, 2, 3, 5], full_attn_layers=[4],
                            num_heads=4, head_dim=16,
                            short_conv_kernel_size=4),
    num_attention_heads=4, num_key_value_heads=4, head_dim=72,
    kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, mla_use_nope=True,
    moe_intermediate_size=32, num_experts=16, num_experts_per_token=4,
    num_shared_experts=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    num_expert_group=1, topk_group=1, use_grouped_topk=True,
    rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=False,
    kda_chunk=16)
SEQ = 64


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several attention blocks and several expert tiles at this size."""
    monkeypatch.setattr(kimi_linear, "BLOCK_Q", 16)
    monkeypatch.setattr(latent, "EXPERT_TILE", 8)


def _cfg(dtype=jnp.float32, **over):
    share = dict(held_first=8, held_count=8, dtype=dtype)
    share.update(over)
    return kimi_linear.KimiLinearConfig.from_hf(HF, **share)


def _sizes(cfg, **more):
    """What the reference reads: the configuration's numbers and which
    share of the router the weights hold."""
    return dict(dataclasses.asdict(cfg),
                expert_rank=cfg.held_first // cfg.held, **more)


def _tokens(seed, n=SEQ):
    return np.random.default_rng(seed).integers(0, HF["vocab_size"], n,
                                                np.int32)


def _run(cfg, params, tokens):
    out = jax.jit(lambda p, t: kimi_linear.forward(p, t[None], cfg))(
        params, tokens)
    return np.asarray(out[0][0]), np.asarray(out[1][0]), np.asarray(out[2])


def _hidden(seed, cfg, rows=SEQ):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (rows, cfg.hidden_size), jnp.float32)


# float32: the two sides differ in the order of their sums and in the
# chunked form of the recurrence (measured 3e-7 to 7e-7 of the logits'
# range, 3e-6 to 7e-6 in a log-probability over three seeds). bfloat16:
# an activation carries 8 bits and a moved expert a quarter of a token's
# routed weight; measured 0.012-0.029 of the logits' range, 0.2-0.45 in
# a log-probability and 8-20 of the ~570 pairs a load counts: the
# tolerances stand 3x over
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,logit_tol,logprob_tol,load_tol", [
    (jnp.float32, 1e-5, 3e-5, 0), (jnp.bfloat16, 0.09, 1.4, 60)],
    ids=["float32", "bfloat16"])
def test_program_against_plain_reference(seed, dtype, logit_tol,
                                         logprob_tol, load_tol):
    cfg = _cfg(dtype)
    params = kimi_linear.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _tokens(seed + 10)
    last, logprobs, load = _run(cfg, params, tokens)
    want = ref.forward(params, tokens, _sizes(cfg), "f32")
    assert load.shape == want[2].shape == (4, 8)
    assert np.abs(last - want[0]).max() \
        <= logit_tol * (want[0].max() - want[0].min())
    assert np.abs(logprobs - want[1]).max() <= logprob_tol
    assert logprobs[-1] == 0 and (logprobs[:-1] < 0).all()
    assert np.abs(load - want[2]).sum() <= load_tol
    assert (want[2].sum(-1) > 0).all()


@pytest.mark.parametrize("without", ["conv", "decay", "beta", "gate",
                                     "shared_key", "router_bias"])
def test_each_mechanism_is_in_the_result(without):
    """The convolutions, the decay, beta, the output gate, the key part
    every head of the latent layer shares (as the projection gave it:
    nothing is rotated on either side) and the router's bias: the
    reference with one of them removed is far from the program, which
    holds them all (it agrees with the whole reference to 3e-5)."""
    cfg = _cfg()
    params = kimi_linear.init_params(cfg, jax.random.PRNGKey(3))
    # a bias that moves choices at this size
    params = dict(params, layers=[
        dict(layer, moe=dict(layer["moe"], bias=layer["moe"]["bias"] * 20))
        if "moe" in layer else layer for layer in params["layers"]])
    tokens = _tokens(13)
    _, logprobs, _ = _run(cfg, params, tokens)
    whole = ref.forward(params, tokens, _sizes(cfg), "f32")
    lacking = ref.forward(params, tokens, _sizes(cfg), "f32",
                          **{without: False})
    assert np.abs(logprobs - whole[1]).max() <= 3e-5
    assert np.abs(logprobs - lacking[1]).max() > 1e-2


def test_the_layers_differ_in_the_kind_of_their_mixer():
    """A KDA layer carries a state: its output at a position depends on
    the order of the earlier rows (the decay and the delta rule do not
    commute) and on rows far behind it. The latent layer encodes no
    position at all: its last row is the same whatever order the
    earlier rows come in."""
    cfg = _cfg()
    params = kimi_linear.init_params(cfg, jax.random.PRNGKey(5))
    assert cfg.kinds == (KDA, KDA, KDA, MLA, KDA)
    kda_layer, mla_layer = params["layers"][1], params["layers"][3]
    assert "A_log" in kda_layer["attn"] and "wkv_a" in mla_layer["attn"]
    h = _hidden(6, cfg)
    order = np.concatenate([np.random.default_rng(0).permutation(SEQ - 1),
                            [SEQ - 1]])
    full = kimi_linear.mla_mix(h, mla_layer, cfg)
    np.testing.assert_allclose(
        kimi_linear.mla_mix(h[order], mla_layer, cfg)[-1], full[-1],
        atol=2e-6)
    kda = kimi_linear.kda_mix(h, kda_layer, cfg)
    shuffled = kimi_linear.kda_mix(h[order], kda_layer, cfg)
    assert float(jnp.abs(shuffled[-1] - kda[-1]).max()) > 1e-3
    # causal: a later row changes nothing before it
    later = kimi_linear.kda_mix(h.at[40:].set(0.0), kda_layer, cfg)
    np.testing.assert_allclose(later[:40], kda[:40], atol=1e-6)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up(seed):
    """Ranks 0 and 1 of 2 of an expert layer (8 of the 16 experts each,
    both through the kernel's path), the shared expert and the residual
    counted once, give the uncut reference's layer: every expert over
    every token and masked; and the shares' loads side by side are the
    uncut reference's choices."""
    full = _cfg(held_first=0, held_count=0)
    layer = kimi_linear.init_params(full, jax.random.PRNGKey(seed))[
        "layers"][2]
    h = _hidden(seed + 20, full)
    eps, m = full.rms_norm_eps, layer["moe"]
    x, chosen, weight = ref_parts._route(
        h, layer["ffn_norm"], m["gate"], m["bias"],
        top=full.num_experts_per_token, scaling=full.routed_scaling_factor,
        eps=eps, precision="f32", select_dtype=None)
    once = ref_parts._dense_mlp(h, layer["ffn_norm"], m["shared"], eps=eps,
                                precision="f32")
    want = once + sum(ref_parts._one_expert(
        x, *(m["experts"][n][e] for n in ("w1", "w3", "w2")), weight[:, e],
        precision="f32") for e in range(16))
    total, loads = once, []
    for rank in range(2):
        part = dict(layer, moe=dict(m, experts=jax.tree.map(
            lambda w: w[8 * rank:8 * rank + 8], m["experts"])))
        out, load = latent.sigmoid_ffn(
            h, part, top=full.num_experts_per_token,
            scale=full.routed_scaling_factor, held_first=8 * rank, eps=eps)
        total = total + (out - once)
        loads.append(np.asarray(load))
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(loads),
                                  np.asarray(chosen.sum(0)))
    assert sum(int(a.sum()) for a in loads) \
        == SEQ * full.num_experts_per_token


def test_the_decoders_share_their_parts():
    """One router, one SwiGLU, one attention half with its output
    projection, one expert layer, one scoring head, one set of latent
    projections; neither this decoder nor GLM-5 names the grouped
    product's parts."""
    assert kimi_linear.rmsnorm is afmoe.rmsnorm is latent.rmsnorm
    assert afmoe.causal_attention_out is latent.causal_attention_out
    assert afmoe.routed_experts is latent.routed_experts
    for module in (kimi_linear, glm_dsa):
        assert not hasattr(module, "grouped_swiglu") \
            and not hasattr(module, "group_by_expert"), module
    # the latent-attention half is latent.mla_attention, for both
    for module in (kimi_linear, glm_dsa):
        assert not hasattr(module, "mla_qkv") \
            and not hasattr(module, "causal_attention_out"), module


def test_config_reads_the_published_keys():
    cfg = kimi_linear.KimiLinearConfig.from_hf(HF, held_first=8,
                                               held_count=8)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.n_moe_layers, cfg.kda_num_heads, cfg.kda_head_dim,
            cfg.kda_conv_kernel, cfg.kv_lora_rank,
            cfg.num_experts_per_token) == (5, 1, 4, 4, 16, 4, 32, 4)
    assert cfg.kda_layers == (1, 2, 3, 5) and cfg.full_attn_layers == (4,)
    assert cfg.kinds == (KDA, KDA, KDA, MLA, KDA)
    assert cfg.held == 8 \
        and kimi_linear.KimiLinearConfig.from_hf(HF).held == 16
    # a top-level size goes before the nested group's (the benchmark's
    # rehearsal overrides top-level numbers only)
    assert kimi_linear.KimiLinearConfig.from_hf(
        dict(HF, kda_num_heads=2)).kda_num_heads == 2
    # the published 27 layers: 20 KDA, 7 full, the last one full
    full = [4, 8, 12, 16, 20, 24, 27]
    big = kimi_linear.KimiLinearConfig.from_hf(dict(
        HF, num_hidden_layers=27, linear_attn_config=dict(
            HF["linear_attn_config"], full_attn_layers=full,
            kda_layers=[i for i in range(1, 28) if i not in full])))
    assert big.kinds.count(KDA) == 20 and big.kinds[-1] == MLA \
        and big.kinds[:4] == (KDA, KDA, KDA, MLA)
    # without the lists every fourth layer is full
    plain = kimi_linear.KimiLinearConfig.from_hf(
        dict(HF, linear_attn_config={}), num_hidden_layers=8)
    assert plain.kinds == ((KDA,) * 3 + (MLA,)) * 2
    with pytest.raises(ValueError, match="outside"):
        kimi_linear.KimiLinearConfig.from_hf(HF, held_first=12, held_count=8)
    with pytest.raises(ValueError, match="each of the 5 layers once"):
        kimi_linear.KimiLinearConfig.from_hf(dict(
            HF, linear_attn_config=dict(HF["linear_attn_config"],
                                        full_attn_layers=[3, 4])))
    with pytest.raises(ValueError, match="q_lora_rank"):
        kimi_linear.KimiLinearConfig.from_hf(dict(HF, q_lora_rank=1536))
    with pytest.raises(ValueError, match="mla_use_nope"):
        kimi_linear.KimiLinearConfig.from_hf(dict(HF, mla_use_nope=False))
    with pytest.raises(ValueError, match="sigmoid"):
        kimi_linear.KimiLinearConfig.from_hf(
            dict(HF, moe_router_activation_func="softmax"))
    with pytest.raises(ValueError, match="unknown option"):
        zoo.build("kimi_linear", hidden="64")


CAPS = ("other/tensors,format=static,num_tensors=1,types=(string)int32,"
        "dimensions=(string)64,framerate=0/1")


@pytest.mark.parametrize("share,grouped", [("", 4)], ids=["whole"])
def test_half_a_router_runs_the_grouped_kernel(share, grouped):
    """``kernel_calls`` names every Pallas kernel of the program: the
    chunked recurrence's two a KDA layer, the attention kernel a block
    of the latent layer's queries, and ``nns_grouped_swiglu`` once an
    expert layer where at least half the router is held (the other
    shares, read off the traced program:
    ``test_grouped.py::test_the_share_picks_the_form``)."""
    p = parse_launch(f'appsrc name=in caps="{CAPS}" ! tensor_filter name=f '
                     f'framework=jax model=zoo://kimi_linear?seq=64&seed=3'
                     f'{share} ! appsink name=out')
    p.start()
    p["in"].push_buffer(Buffer.from_arrays([_tokens(0)]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    report = p["f"].transfer_report()
    p.stop()
    # 4 KDA layers; the one MLA layer x 4 blocks of 16 queries
    assert report["kernel_calls"] == {
        "nns_kda_chunk_intra": 4, "nns_kda_chunk_state": 4,
        "nns_masked_attention": 4, "nns_grouped_swiglu": grouped}


@pytest.mark.parametrize("window", ["", "in-flight=4 prefetch-host=true"],
                         ids=["window1", "window4"])
def test_pipeline_gives_the_direct_calls_three_tensors(window):
    uri = "zoo://kimi_linear?seq=64&held_first=8&held_count=8&seed=3"
    apply_fn, params, in_info, out_info = zoo.build(
        "kimi_linear", seq="64", held_first="8", held_count="8", seed="3")
    assert [tuple(i.shape) for i in out_info] == [(64,), (64,), (4, 8)]
    frames = [_tokens(i) for i in range(5)]
    want = [jax.jit(apply_fn)(params, f) for f in frames]
    p = parse_launch(f'appsrc name=in caps="{CAPS}" ! tensor_filter name=f '
                     f'framework=jax model={uri} {window} ! appsink name=out')
    p.start()
    for f in frames:
        p["in"].push_buffer(Buffer.from_arrays([f]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    got = [[np.asarray(c.host()) for c in b.chunks] for b in p["out"].buffers]
    report = p["f"].transfer_report()
    p.stop()
    assert report["kernel_calls"]["nns_grouped_swiglu"] == 4
    assert report.get("prepared_leaves", 0) == 0
    assert len(got) == 5
    for g, w in zip(got, want):
        assert [x.dtype for x in g] == [np.float32, np.float32, np.int32]
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, np.asarray(b))
