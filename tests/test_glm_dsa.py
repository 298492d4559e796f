"""``models/glm_dsa.py`` (latent attention, the sparse-attention indexer,
the sigmoid router over a share of the experts) at a tiny size on the
CPU with seeded weights, against the benchmark's plain reference
(``benchmark/refs/glm_dsa.py``, which imports nothing of the program)
and against hand-worked values. The ops under it have files of their
own: ``test_grouped.py``, ``test_sparse_attention.py``."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.models import glm_dsa, latent, zoo
from nnstreamer_tpu.ops.sparse_attention import (blocked_causal_attention,
                                                 topk_mask)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from refs import glm_dsa as ref  # noqa: E402

# the configuration's rehearsal sizes (benchmark/configs/glm5_ep16_l5.json):
# sequences of 64 over an index_topk of 16, so the selection is live
SIZES = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, index_n_heads=2,
    index_head_dim=8, index_topk=16, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=32, num_experts_per_tok=4,
    num_hidden_layers=3, first_k_dense_replace=1, vocab_size=64,
    rms_norm_eps=1e-5, rope_theta=1e6, routed_scaling_factor=2.5)
SEQ = 64
RANK1 = dict(SIZES, expert_rank=1)      # the reference's experts 8..15


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several attention blocks and several expert tiles at this size."""
    monkeypatch.setattr(glm_dsa, "BLOCK_Q", 16)
    monkeypatch.setattr(latent, "EXPERT_TILE", 8)


def _cfg(dtype=jnp.float32, **over):
    share = dict(held_first=8, held_count=8, dtype=dtype)
    share.update(over)
    return glm_dsa.GLMDSAConfig.from_hf(SIZES, **share)


def _tokens(seed, n=SEQ):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n,
                                                np.int32)


def _run(cfg, params, tokens):
    out = jax.jit(lambda p, t: glm_dsa.forward(p, t[None], cfg))(params,
                                                                 tokens)
    return np.asarray(out[0][0]), np.asarray(out[1][0]), np.asarray(out[2])


# bfloat16: an activation carries 8 bits, and at this size one key moved
# across the 16th place or one expert across the 4th is a sixteenth of a
# token's attention or a quarter of its routed part; measured 0.03-0.15 of
# the logits' range over these seeds, against 5e-7 in float32
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,logit_tol,logprob_tol,load_tol", [
    (jnp.float32, 1e-4, 1e-4, 0), (jnp.bfloat16, 0.3, 1.5, 16)],
    ids=["float32", "bfloat16"])
def test_program_against_plain_reference(seed, dtype, logit_tol,
                                         logprob_tol, load_tol):
    cfg = _cfg(dtype)
    params = glm_dsa.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _tokens(seed + 10)
    last, logprobs, load = _run(cfg, params, tokens)
    want = ref.forward(params, tokens, RANK1, "f32")
    assert load.shape == want[2].shape == (2, 8)
    assert np.abs(last - want[0]).max() \
        <= logit_tol * (want[0].max() - want[0].min())
    assert np.abs(logprobs - want[1]).max() <= logprob_tol
    assert logprobs[-1] == 0 and (logprobs[:-1] < 0).all()
    assert np.abs(load - want[2]).sum() <= load_tol


@pytest.mark.parametrize("seed", [0, 1])
def test_below_index_topk_is_plain_causal_mla(seed):
    """A sequence no longer than ``index_topk`` selects every causal
    key: the outputs are those of a model whose indexer can drop
    nothing, and the indexer's own weights do not matter."""
    cfg = _cfg()
    params = glm_dsa.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _tokens(seed, n=cfg.index_topk)
    got = _run(cfg, params, tokens)
    scrambled = jax.tree.map(lambda x: x, params)
    for layer in scrambled["layers"]:
        layer["indexer"] = jax.tree.map(lambda x: -3.0 * x + 1.0,
                                        layer["indexer"])
    for a, b in zip(got, _run(cfg, scrambled, tokens)):
        np.testing.assert_array_equal(a, b)
    # and above it the selection is live: the indexer's weights matter
    tokens = _tokens(seed)
    assert np.abs(_run(cfg, params, tokens)[1]
                  - _run(cfg, scrambled, tokens)[1]).max() > 1e-3


def _ref_selection(scores, k, valid):
    return np.asarray(valid & ref._top_rank(
        jnp.where(valid, scores, -jnp.inf), k))


@pytest.mark.parametrize("case", ["random", "ties", "zeros", "short_rows"])
def test_topk_mask_selects_the_reference_set(case):
    """The exact k largest of each row's valid entries, ties to the
    lower index: the set a stable descending sort gives (the plain
    reference's selection), on scores with no ties, many ties, signed
    zeros, and rows that hold fewer than k."""
    rng = np.random.default_rng(7)
    t, s, k = 48, 64, 16
    scores = rng.standard_normal((t, s)).astype(np.float32)
    if case == "ties":
        scores = np.round(scores * 2) / 2
    elif case == "zeros":
        scores = np.where(rng.random((t, s)) < 0.6, 0.0, scores)
        scores = np.where(rng.random((t, s)) < 0.3, -0.0, scores
                          ).astype(np.float32)
    valid = np.arange(s - t, s)[:, None] >= np.arange(s)[None, :]
    if case == "short_rows":
        valid = np.arange(t)[:, None] >= np.arange(s)[None, :]
    got = np.asarray(jax.jit(lambda x, v: topk_mask(x, k, v))(scores, valid))
    np.testing.assert_array_equal(got, _ref_selection(scores, k, valid))
    np.testing.assert_array_equal(got.sum(-1),
                                  np.minimum(valid.sum(-1), k))


@pytest.mark.parametrize("seed", [0, 1])
def test_sparse_regime_selects_the_reference_set(seed):
    """Above ``index_topk`` the program's selection, from its own
    float32 index scores, is the reference's set row for row."""
    cfg = _cfg()
    layer = glm_dsa.init_params(cfg, jax.random.PRNGKey(seed))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(seed + 5),
                          (SEQ, cfg.hidden_size), jnp.float32)
    pos = jnp.arange(SEQ)
    x = latent.rmsnorm(h, layer["attn_norm"], cfg.rms_norm_eps)
    c_q, _, _, _ = latent.mla_qkv(x, layer["attn"], pos, cfg)
    q_i, k_i, w = glm_dsa.indexer_qkw(x, c_q, layer["indexer"], pos, cfg)
    scores = glm_dsa.index_scores(q_i, k_i, w)
    causal = np.tril(np.ones((SEQ, SEQ), bool))
    got = np.asarray(topk_mask(scores, cfg.index_topk, causal))
    np.testing.assert_array_equal(
        got, _ref_selection(scores, cfg.index_topk, causal))
    assert got[cfg.index_topk:].sum(-1).tolist() \
        == [cfg.index_topk] * (SEQ - cfg.index_topk)
    assert not got[-1].all() and (got <= causal).all()


@pytest.mark.parametrize("block_q", [16, 64, 24])
def test_mla_is_per_head_attention_over_expanded_keys(block_q):
    """The latent projections and the blocked attention against each
    head's keys and values written out: ``k_h = [c_kv W_kvb_h(nope) |
    RoPE(k_r)]``, ``v_h = c_kv W_kvb_h(v)``, dense causal softmax."""
    cfg = _cfg()
    a = glm_dsa.init_params(cfg, jax.random.PRNGKey(3))["layers"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (SEQ, cfg.hidden_size))
    pos = jnp.arange(SEQ)
    _, q, k, v = latent.mla_qkv(x, a, pos, cfg)
    got = blocked_causal_attention(q, k, v, scale=16 ** -0.5,
                                   block_q=block_q)
    nope, rp, vd, r = 12, 4, 16, cfg.kv_lora_rank
    kv = x @ a["wkv_a"]
    c_kv = latent.rmsnorm(kv[:, :r], a["kv_norm"], cfg.rms_norm_eps)
    k_r = glm_dsa.rope_interleaved(kv[:, r:], pos, cfg.rope_theta)
    wkv_b = a["wkv_b"].reshape(r, cfg.num_attention_heads, nope + vd)
    causal = np.tril(np.ones((SEQ, SEQ), bool))
    for h in range(cfg.num_attention_heads):
        k_h = jnp.concatenate([c_kv @ wkv_b[:, h, :nope], k_r], -1)
        v_h = c_kv @ wkv_b[:, h, nope:]
        scores = (q[:, h, :nope + rp] @ k_h.T) * (nope + rp) ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        np.testing.assert_allclose(got[:, h], probs @ v_h, atol=2e-5)


def test_rope_interleaved_rotates_pairs():
    x = jnp.asarray([[1.0, 0.0, 0.0, 2.0]])[None]          # [1, 1, 4]
    x = jnp.concatenate([x, x], 0)                         # positions 0, 1
    out = np.asarray(glm_dsa.rope_interleaved(x, jnp.arange(2), 100.0))
    np.testing.assert_allclose(out[0, 0], [1, 0, 0, 2], atol=1e-6)
    # position 1: pair 0 turns by 1 rad, pair 1 by 100 ** -0.5 = 0.1 rad
    np.testing.assert_allclose(
        out[1, 0], [np.cos(1), np.sin(1), -2 * np.sin(0.1),
                    2 * np.cos(0.1)], atol=1e-6)


@pytest.mark.parametrize("d,nope,rope", [
    (16, 12, 4),        # the toy head as published
    (128, 12, 4),       # the toy head padded to the kernel's lanes
    (256, 128, 64),     # LongCat's head, 192 wide, padded
    (256, 192, 64),     # GLM-5's: nothing past the rotation
])
def test_rope_columns_rotates_the_roped_columns_alone(d, nope, rope):
    """``rope`` columns from ``nope`` on are ``rope_interleaved``'s; the
    ones before and the padding after come back bit for bit (cos 1, sin
    0), so zero padding stays zero."""
    x = jax.random.normal(jax.random.PRNGKey(d + nope), (3, 8, d))
    x = x.at[..., nope + rope:].set(0.0)
    pos = jnp.arange(5, 13)
    got = latent.rope_columns(x, pos, 1e4, nope, rope)
    assert got.shape == x.shape and got.dtype == x.dtype
    turned = latent.rope_interleaved(
        jnp.swapaxes(x[..., nope:nope + rope], 0, 1), pos, 1e4)
    np.testing.assert_allclose(got[..., nope:nope + rope],
                               jnp.swapaxes(turned, 0, 1), atol=1e-6)
    np.testing.assert_array_equal(got[..., :nope], x[..., :nope])
    assert not np.asarray(got[..., nope + rope:]).any()


def test_router_bias_moves_the_choice_not_the_weight():
    """Hand-worked row: sigmoid scores (0.9, 0.8, 0.7, 0.1), two
    chosen, scaling 2.5. Without a bias experts 0 and 1 are chosen, with
    weights 0.9 / 1.7 x 2.5 and 0.8 / 1.7 x 2.5. A bias of 0.15 on
    expert 2 lifts it over expert 1 (0.85 > 0.8); the weights are still
    of the plain scores: 0.9 / 1.6 x 2.5 and 0.7 / 1.6 x 2.5."""
    s = np.asarray([0.9, 0.8, 0.7, 0.1])
    gate = np.zeros((4, 4), np.float32)
    gate[0] = np.log(s / (1 - s))
    x = jnp.asarray([[1.0, 0, 0, 0]], jnp.float32)
    choice, weight = latent.sigmoid_route(
        x, {"gate": jnp.asarray(gate), "bias": jnp.zeros(4)}, 2, 2.5)
    assert choice.tolist() == [[0, 1]]
    np.testing.assert_allclose(weight, [[0.9 / 1.7 * 2.5, 0.8 / 1.7 * 2.5]],
                               rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.15, 0.0])
    choice, weight = latent.sigmoid_route(
        x, {"gate": jnp.asarray(gate), "bias": bias}, 2, 2.5)
    assert choice.tolist() == [[0, 2]]
    np.testing.assert_allclose(weight, [[0.9 / 1.6 * 2.5, 0.7 / 1.6 * 2.5]],
                               rtol=1e-6)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up(seed):
    """The routed parts of all four ranks' shares plus the shared expert
    once are the uncut layer's output (the whole router held: the
    kernel's path; a quarter each: the tile loops), and the ranks' loads
    side by side are the uncut layer's load: every token-expert pair is
    served by exactly one rank."""
    full = _cfg(held_first=0, held_count=0)
    layer = glm_dsa.init_params(full, jax.random.PRNGKey(seed))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(seed + 20),
                          (SEQ, full.hidden_size), jnp.float32)

    def ffn(layer, cfg):
        return latent.sigmoid_ffn(
            h, layer, top=cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor, held_first=cfg.held_first,
            eps=cfg.rms_norm_eps)

    whole, load = ffn(layer, full)
    shared = latent.swiglu(
        latent.rmsnorm(h, layer["ffn_norm"], full.rms_norm_eps),
        layer["moe"]["shared"])
    total, loads = h + shared, []
    for rank in range(4):
        cfg = _cfg(held_first=8 * rank, held_count=8)
        part = dict(layer, moe=dict(layer["moe"], experts=jax.tree.map(
            lambda w: w[8 * rank:8 * rank + 8], layer["moe"]["experts"])))
        out, load_r = ffn(part, cfg)
        total = total + (out - h - shared)
        loads.append(np.asarray(load_r))
    np.testing.assert_allclose(total, whole, atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(loads), load)
    assert int(load.sum()) == SEQ * full.num_experts_per_tok


def test_config_reads_the_hf_keys():
    hf = dict(SIZES, rope_parameters={"rope_theta": 5e5}, model_type="x")
    del hf["rope_theta"]
    cfg = glm_dsa.GLMDSAConfig.from_hf(hf, held_first=4, held_count=4)
    assert cfg.rope_theta == 5e5 and cfg.held == 4 and cfg.n_moe_layers == 2
    assert glm_dsa.GLMDSAConfig.from_hf(SIZES).held == 32
    with pytest.raises(ValueError):
        glm_dsa.GLMDSAConfig.from_hf(SIZES, held_first=30, held_count=8)
    with pytest.raises(ValueError):
        zoo.build("glm_dsa", hidden="64")


@pytest.mark.parametrize("window", ["", "in-flight=2 prefetch-host=true"],
                         ids=["sync", "windowed"])
def test_pipeline_gives_the_direct_calls_three_tensors(window):
    uri = "zoo://glm_dsa?seq=64&held_first=8&held_count=8&seed=3"
    apply_fn, params, in_info, out_info = zoo.build(
        "glm_dsa", seq="64", held_first="8", held_count="8", seed="3")
    assert [tuple(i.shape) for i in out_info] == [(64,), (64,), (2, 8)]
    frames = [_tokens(i) for i in range(3)]
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
    assert p["f"].transfer_report().get("prepared_leaves", 0) == 0
    p.stop()
    assert len(got) == 3
    for g, w in zip(got, want):
        assert [x.dtype for x in g] == [np.float32, np.float32, np.int32]
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, np.asarray(b))


@pytest.mark.parametrize("uri,dims,calls,equations", [
    # 3 layers x 4 blocks of 16 queries (the fixture's BLOCK_Q); a
    # quarter of the router held: the split form's two kernels twice
    # an expert layer (ops/grouped.py::takes_split); an attention's 2
    # reshapes, 2 slices, 2 pads and 3 transposes (latent.mla_weights),
    # an expert layer's router bias to float32
    ("zoo://glm_dsa?seq=64&held_first=8&held_count=8",
     ("int32", "64"), {"nns_masked_attention": 12, "nns_grouped_gate_up": 4,
                       "nns_grouped_down": 4}, 3 * 9 + 2),
    # in float32 the bias is used as it is loaded
    ("zoo://glm_dsa?seq=64&held_first=8&held_count=8&dtype=float32",
     ("int32", "64"), {"nns_masked_attention": 12, "nns_grouped_gate_up": 4,
                       "nns_grouped_down": 4}, 3 * 9),
    ("zoo://mlp", ("float32", "64:4"), {}, None),
    # every expert of the router held: the share takes the grouped kernel,
    # once an expert layer (ops/grouped.py::takes_kernel)
    ("zoo://glm_dsa?seq=64", ("int32", "64"),
     {"nns_masked_attention": 12, "nns_grouped_swiglu": 2}, 3 * 9 + 2),
], ids=["glm_dsa", "glm_dsa_float32", "plain_xla", "glm_dsa_whole_router"])
def test_backend_reports_the_kernels_it_calls(uri, dims, calls, equations):
    """``kernel_calls`` beside ``prepared_leaves``: the kernel's name
    with its call sites in the traced program, nothing for a model in
    plain XLA; ``prepared_equations``: what the load took over of the
    projections' weights (no leaf held narrower: ``prepared_leaves``
    0), absent with the rest of the block where nothing is prepared."""
    caps = (f"other/tensors,format=static,num_tensors=1,types=(string)"
            f"{dims[0]},dimensions=(string){dims[1]},framerate=0/1")
    p = parse_launch(f'appsrc name=in caps="{caps}" ! tensor_filter name=f '
                     f'framework=jax model={uri} in-flight=2 ! '
                     f'appsink name=out')
    p.start()
    shape = tuple(int(d) for d in reversed(dims[1].split(":")))
    p["in"].push_buffer(Buffer.from_arrays([np.zeros(shape, dims[0])]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    report = p["f"].transfer_report()
    p.stop()
    assert report["kernel_calls"] == calls
    assert report["prepared_leaves"] == report["prepared_bytes"] == 0
    assert report["prepared_equations"] == (equations or 0)
