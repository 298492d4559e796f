"""``models/longcat.py`` (the shortcut-connected double block: two latent
attentions, two dense MLPs, an expert layer beside them whose softmax
router ends in identity experts) at a tiny size on the CPU with seeded
weights, against the benchmark's plain reference
(``benchmark/refs/longcat.py``, which imports nothing of the program)
and against hand-worked values."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.models import glm_dsa, latent, longcat, zoo

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))
from refs import longcat as ref  # noqa: E402

# the configuration's rehearsal sizes (benchmark/configs/longcat_ep32_l4
# .json): a router of 32 real + 16 identity experts choosing 6, so a
# token's choice holds real and identity experts alike
SIZES = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, n_routed_experts=32, zero_expert_num=16,
    zero_expert_type="identity", moe_topk=6, num_layers=2, vocab_size=64,
    rms_norm_eps=1e-5, rope_theta=1e7, routed_scaling_factor=6)
SEQ = 64
# the reference's view of the same share: real experts 4..7 of 32
RANK1 = dict(SIZES, n_routed_experts_total=32, expert_rank=1)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several attention blocks and several expert tiles at this size."""
    monkeypatch.setattr(longcat, "BLOCK_Q", 16)
    monkeypatch.setattr(longcat, "EXPERT_TILE", 8)


def _cfg(dtype=jnp.float32, **over):
    share = dict(held_first=4, held_count=4, dtype=dtype)
    share.update(over)
    return longcat.LongCatConfig.from_hf(SIZES, **share)


def _tokens(seed, n=SEQ):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n,
                                                np.int32)


def _run(cfg, params, tokens):
    out = jax.jit(lambda p, t: longcat.forward(p, t[None], cfg))(params,
                                                                 tokens)
    return np.asarray(out[0][0]), np.asarray(out[1][0]), np.asarray(out[2])


def _hidden(seed, cfg, rows=SEQ):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (rows, cfg.hidden_size), jnp.float32)


# float32: the two sides differ in the order of their sums (measured
# 4e-7 of the logits' range, 3e-6 in a log-probability over six seeds).
# bfloat16: an activation carries 8 bits, and one expert moved across
# the 6th place changes a sixth of a token's routed weight; measured
# 0.005-0.016 of the logits' range, 0.03-0.12 in a log-probability and
# 2-5 of the ~300 pairs a load counts: the tolerances stand 3x over
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,logit_tol,logprob_tol,load_tol", [
    (jnp.float32, 1e-5, 3e-5, 0), (jnp.bfloat16, 0.05, 0.4, 16)],
    ids=["float32", "bfloat16"])
def test_program_against_plain_reference(seed, dtype, logit_tol,
                                         logprob_tol, load_tol):
    cfg = _cfg(dtype)
    params = longcat.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _tokens(seed + 10)
    last, logprobs, load = _run(cfg, params, tokens)
    want = ref.forward(params, tokens, RANK1, "f32")
    assert load.shape == want[2].shape == (2, 5)
    assert np.abs(last - want[0]).max() \
        <= logit_tol * (want[0].max() - want[0].min())
    assert np.abs(logprobs - want[1]).max() <= logprob_tol
    assert logprobs[-1] == 0 and (logprobs[:-1] < 0).all()
    assert np.abs(load - want[2]).sum() <= load_tol
    # real and identity experts are both chosen, and held ones are hit
    assert (want[2][:, :-1].sum(-1) > 0).all() and (want[2][:, -1] > 0).all()
    assert (want[2][:, -1] < SEQ * cfg.moe_topk).all()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up(seed):
    """Over all eight ranks of the split, the held experts' parts plus
    the identity part, which every rank computes alike, counted once,
    are the uncut layer's ``MoE(x)``; the ranks' loads side by side
    with the identity column once are the uncut layer's load: every
    pair is served by exactly one rank or by an identity expert."""
    full = _cfg(held_first=0, held_count=0)
    m = longcat.init_params(full, jax.random.PRNGKey(seed))["layers"][1]["moe"]
    x = _hidden(seed + 20, full)
    whole, load = longcat.moe(x, m, full)
    none_held = dict(m, experts=jax.tree.map(lambda w: w[:1] * 0,
                                             m["experts"]))
    identity, _ = longcat.moe(x, none_held, _cfg(held_first=0, held_count=1))
    total, loads = identity, []
    for rank in range(8):
        part = dict(m, experts=jax.tree.map(
            lambda w: w[4 * rank:4 * rank + 4], m["experts"]))
        out, load_r = longcat.moe(x, part, _cfg(held_first=4 * rank))
        total = total + (out - identity)
        loads.append(np.asarray(load_r))
        assert load_r[-1] == load[-1]
    np.testing.assert_allclose(total, whole, atol=2e-5)
    np.testing.assert_array_equal(
        np.concatenate([r[:-1] for r in loads] + [loads[0][-1:]]), load)
    assert int(load.sum()) == SEQ * full.moe_topk
    # and the uncut layer is the reference's with every expert held
    want, want_load = ref.moe(x, m, dict(SIZES, n_routed_experts_total=32))
    np.testing.assert_allclose(whole, want, atol=2e-5)
    np.testing.assert_array_equal(load, want_load)


def _router(p, **over):
    """A configuration and classifier whose softmax over four outputs
    (two real, two identity) is ``p`` for the token ``[1, 0, 0, 0]``."""
    cfg = longcat.LongCatConfig(hidden_size=4, n_routed_experts=2,
                                zero_expert_num=2, moe_topk=2, **over)
    gate = np.zeros((4, 4), np.float32)
    gate[0] = np.log(p)
    return cfg, jnp.asarray(gate), jnp.asarray([[1.0, 0, 0, 0]], jnp.float32)


def test_router_bias_moves_the_choice_not_the_weight():
    """Hand-worked row: softmax scores (0.4, 0.3, 0.2, 0.1), two chosen,
    scaling 6. Without a bias outputs 0 and 1 are chosen with weights
    6 x 0.4 and 6 x 0.3: the softmax's own, not renormalised over the
    chosen (that would give 6 x 0.4 / 0.7). A bias of 0.15 on output 2
    lifts it over output 1 (0.35 > 0.3); its weight is still 6 x 0.2."""
    cfg, gate, x = _router(np.asarray([0.4, 0.3, 0.2, 0.1]))
    choice, weight = longcat.route(x, {"gate": gate, "bias": jnp.zeros(4)},
                                   cfg)
    assert choice.tolist() == [[0, 1]]
    np.testing.assert_allclose(weight, [[2.4, 1.8]], rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.15, 0.0])
    choice, weight = longcat.route(x, {"gate": gate, "bias": bias}, cfg)
    assert choice.tolist() == [[0, 2]]
    np.testing.assert_allclose(weight, [[2.4, 1.2]], rtol=1e-6)


def test_identity_experts_cost_no_product():
    """A token whose chosen experts are all identities gets
    ``routed_scaling_factor x sum(p) x`` and runs no product: the
    grouped loops turn zero times (NaN weights would poison any turn),
    and the load counts its pairs in the last column alone. One whose
    choice is half real gets ``g_0 SwiGLU_0(x) + g_2 x``."""
    cfg, gate, x = _router(np.asarray([0.1, 0.05, 0.45, 0.4]))
    experts = {n: jnp.full((2,) + s, jnp.nan) for n, s in (
        ("w1", (4, 8)), ("w3", (4, 8)), ("w2", (8, 4)))}
    m = {"gate": gate, "bias": jnp.zeros(4), "experts": experts}
    out, load = jax.jit(lambda x, m: longcat.moe(x, m, cfg))(x, m)
    np.testing.assert_allclose(out, 6 * (0.45 + 0.4) * x, rtol=1e-6)
    assert load.tolist() == [0, 0, 2]
    cfg, gate, x = _router(np.asarray([0.4, 0.1, 0.3, 0.2]))
    rng = np.random.default_rng(0)
    experts = {n: jnp.asarray(rng.standard_normal((2,) + s), jnp.float32)
               for n, s in (("w1", (4, 8)), ("w3", (4, 8)), ("w2", (8, 4)))}
    m = {"gate": gate, "bias": jnp.zeros(4), "experts": experts}
    out, load = longcat.moe(x, m, cfg)
    one = latent.swiglu(x, jax.tree.map(lambda w: w[0], experts))
    np.testing.assert_allclose(out, 6 * 0.4 * one + 6 * 0.3 * x, rtol=1e-5)
    assert load.tolist() == [1, 0, 1]


def test_shortcut_is_computed_from_x1_and_added_after_the_second_ffn():
    """The block against its seven equations written out with the
    module's parts, and against two wrong placements: the expert layer
    fed ``x2`` (an ordinary block), and its result added before the
    second attention reads the stream."""
    cfg = _cfg()
    layer = longcat.init_params(cfg, jax.random.PRNGKey(4))["layers"][0]
    h0 = _hidden(5, cfg)
    first, second = layer["sub"]

    def norm(a, sub):
        return longcat.rmsnorm(a, sub["ffn_norm"], cfg.rms_norm_eps)

    def layer_with(moe_from, add_before_second_attention=False):
        a1 = longcat.attend(h0, first, cfg)
        x1 = norm(a1, first)
        h1 = a1 + latent.swiglu(x1, first["mlp"])
        if moe_from == "x1":
            m, _ = longcat.moe(x1, layer["moe"], cfg)
        if add_before_second_attention:
            h1 = h1 + m
        a2 = longcat.attend(h1, second, cfg)
        x2 = norm(a2, second)
        if moe_from == "x2":
            m, _ = longcat.moe(x2, layer["moe"], cfg)
        h2 = a2 + latent.swiglu(x2, second["mlp"])
        return h2 if add_before_second_attention else h2 + m

    got, _ = longcat.block(h0[None], layer, cfg)
    np.testing.assert_allclose(got[0], layer_with("x1"), atol=2e-5)
    scale = float(jnp.abs(got).max())
    assert float(jnp.abs(got[0] - layer_with("x2")).max()) > 1e-2 * scale
    assert float(jnp.abs(got[0] - layer_with("x1", True)).max()) \
        > 1e-2 * scale


@pytest.mark.parametrize("flag", ["mla_scale_q_lora", "mla_scale_kv_lora"])
def test_the_two_mla_scales_are_applied(flag):
    """``sqrt(hidden / q_lora_rank) = sqrt(2)`` on the query latent,
    ``sqrt(hidden / kv_lora_rank) = 2`` on the key-value latent, after
    their norms: the projections with a flag on are those with it off
    times the factor, the roped key part and the other side untouched;
    the reference agrees on the whole model either way, so neither side
    drops a flag."""
    cfg, off = _cfg(), _cfg(**{flag: False})
    assert (cfg.q_scale, cfg.kv_scale) == (2 ** 0.5, 2.0)
    a = longcat.init_params(cfg, jax.random.PRNGKey(6))["layers"][0]["sub"][0]
    x, pos = _hidden(7, cfg), jnp.arange(SEQ)

    def qkv(c):
        return latent.mla_qkv(x, a["attn"], pos, c, q_scale=c.q_scale,
                              kv_scale=c.kv_scale)

    (c_q, q, k, v), (c_q0, q0, k0, v0) = qkv(cfg), qkv(off)
    nope = cfg.qk_nope_head_dim
    if flag == "mla_scale_q_lora":
        np.testing.assert_allclose(c_q, c_q0 * 2 ** 0.5, rtol=1e-6)
        np.testing.assert_allclose(q, q0 * 2 ** 0.5, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(k, k0)
        np.testing.assert_array_equal(v, v0)
    else:
        np.testing.assert_array_equal(q, q0)
        np.testing.assert_allclose(v, v0 * 2, rtol=1e-6)
        np.testing.assert_allclose(k[..., :nope], k0[..., :nope] * 2,
                                   rtol=1e-6)
        np.testing.assert_array_equal(k[..., nope:], k0[..., nope:])
    params = longcat.init_params(off, jax.random.PRNGKey(8))
    tokens = _tokens(9)
    on_side, off_side = (_run(c, params, tokens) for c in (cfg, off))
    assert np.abs(on_side[1] - off_side[1]).max() > 1e-2
    want = ref.forward(params, tokens, dict(RANK1, **{flag: False}), "f32")
    np.testing.assert_allclose(off_side[1], want[1], atol=1e-4)


def test_bfloat16_scale_is_not_rounded_to_the_stream():
    """The factor stays float32: ``sqrt(12)`` as a bfloat16 is 3.46875,
    0.13 % over, which would lean every key and value one way."""
    x = jnp.arange(1, 2, 1 / 128, dtype=jnp.bfloat16)    # every one exact
    got = latent._scaled(x, 12 ** 0.5)
    assert got.dtype == jnp.bfloat16
    exact = np.asarray(x, np.float64) * 12 ** 0.5
    np.testing.assert_array_equal(
        got, (np.asarray(x, np.float32) * np.float32(12 ** 0.5)
              ).astype(jnp.bfloat16))
    lean = np.asarray(got, np.float64) / exact - 1
    weak = np.asarray(x * 12 ** 0.5, np.float64) / exact - 1
    assert abs(lean.mean()) < 2e-4 and weak.mean() > 1e-3
    assert latent._scaled(x, 1.0) is x


def test_both_decoders_share_the_latent_parts():
    """One copy of the latent projections, the rotations, the SwiGLU
    and the attention half: ``models/glm_dsa.py`` and
    ``models/longcat.py`` name the same function objects."""
    for name in ("mla_qkv", "swiglu", "causal_attention_out", "rmsnorm",
                 "group_by_expert", "grouped_swiglu"):
        assert getattr(glm_dsa, name) is getattr(longcat, name), name
    assert glm_dsa.rope_interleaved is latent.rope_interleaved
    assert glm_dsa.BLOCK_Q == latent.BLOCK_Q == 512
    assert glm_dsa.EXPERT_TILE == latent.EXPERT_TILE == 256


def test_config_reads_the_published_keys():
    hf = dict(SIZES, attention_method="MLA", max_position_embeddings=131072,
              attention_bias=False)
    cfg = longcat.LongCatConfig.from_hf(hf, held_first=8, held_count=4)
    assert (cfg.num_layers, cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size,
            cfg.moe_topk, cfg.zero_expert_num) == (2, 128, 32, 6, 16)
    assert cfg.mla_scale_q_lora and cfg.mla_scale_kv_lora
    assert cfg.held == 4 and cfg.router_width == 48
    assert longcat.LongCatConfig.from_hf(hf).held == 32
    plain = longcat.LongCatConfig.from_hf(dict(hf, mla_scale_q_lora=False,
                                               mla_scale_kv_lora=False))
    assert plain.q_scale == plain.kv_scale == 1.0
    with pytest.raises(ValueError, match="outside"):
        longcat.LongCatConfig.from_hf(hf, held_first=30, held_count=4)
    with pytest.raises(ValueError, match="identity"):
        longcat.LongCatConfig.from_hf(dict(hf, zero_expert_type="copy"))
    with pytest.raises(ValueError, match="unknown option"):
        zoo.build("longcat", hidden="64")


@pytest.mark.parametrize("window", ["", "in-flight=4 prefetch-host=true"],
                         ids=["window1", "window4"])
def test_pipeline_gives_the_direct_calls_three_tensors(window):
    uri = "zoo://longcat?seq=64&held_first=4&held_count=4&seed=3"
    apply_fn, params, in_info, out_info = zoo.build(
        "longcat", seq="64", held_first="4", held_count="4", seed="3")
    assert [tuple(i.shape) for i in out_info] == [(64,), (64,), (2, 5)]
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
    # 2 layers x 2 attentions x 4 blocks of 16 queries (the fixture's)
    assert report["kernel_calls"] == {"nns_masked_attention": 16}
    assert report.get("prepared_leaves", 0) == 0
    # an attention's 2 reshapes, 2 slices, 2 pads and 3 transposes
    # (latent.mla_weights), and a router bias's conversion a layer
    assert report["prepared_equations"] == 4 * 9 + 2
    assert len(got) == 5
    for g, w in zip(got, want):
        assert [x.dtype for x in g] == [np.float32, np.float32, np.int32]
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, np.asarray(b))


# -- the projections' weights laid out for the kernel (PR 33) -------------

def _glm_cfg(**over):
    return glm_dsa.GLMDSAConfig(held_first=8, held_count=8,
                                dtype=jnp.float32, **over)


# a decoder's module, its toy configuration (a head 12 + 4 = 16 columns
# wide: no multiple of the kernel's 128 lanes) and where its first
# attention sublayer's leaves lie
DECODERS = {
    "longcat": (longcat, _cfg, lambda p: p["layers"][0]["sub"][0]["attn"]),
    "glm_dsa": (glm_dsa, _glm_cfg, lambda p: p["layers"][0]["attn"]),
}


def _plain_mla_qkv(x, a, positions, cfg, *, q_scale=1.0, kv_scale=1.0):
    """The latent projections the plainest way, a head's columns as
    published: ``[nope | rope]`` concatenated and nothing padded, the
    weights multiplied as they are loaded. What ``latent.mla_qkv``
    returned before it padded a head."""
    h, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    rp, r = cfg.qk_rope_head_dim, cfg.kv_lora_rank
    c_q = latent._scaled(latent.rmsnorm(
        x @ a["wq_a"], a["q_norm"], cfg.rms_norm_eps), q_scale)
    q = (c_q @ a["wq_b"]).reshape(-1, h, nope + rp)
    q = jnp.concatenate([q[..., :nope], latent.rope_interleaved(
        q[..., nope:], positions, cfg.rope_theta)], -1)
    kv = x @ a["wkv_a"]
    c_kv = latent._scaled(latent.rmsnorm(
        kv[:, :r], a["kv_norm"], cfg.rms_norm_eps), kv_scale)
    k_r = latent.rope_interleaved(kv[:, r:], positions, cfg.rope_theta)
    kv_h = (c_kv @ a["wkv_b"]).reshape(-1, h, a["wkv_b"].shape[1] // h)
    k = jnp.concatenate([kv_h[..., :nope], jnp.broadcast_to(
        k_r[:, None], (x.shape[0], h, rp))], -1)
    return c_q, q, k, kv_h[..., nope:]


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_mla_qkv_pads_a_head_to_the_lane_width(name):
    """q and k come out 128 wide, as the attention kernel reads a head:
    the real 16 columns are the concatenated form's, the 112 past them
    are zero (zero weight rows, cos 1 and sin 0 in the rotation, nothing
    added by the roped key part); v is not widened."""
    model, make, attn = DECODERS[name]
    cfg = make()
    a = attn(model.init_params(cfg, jax.random.PRNGKey(11)))
    x, pos = _hidden(12, cfg), jnp.arange(SEQ)
    scales = dict(q_scale=2 ** 0.5, kv_scale=2.0) if name == "longcat" else {}
    got = latent.mla_qkv(x, a, pos, cfg, **scales)
    want = _plain_mla_qkv(x, a, pos, cfg, **scales)
    d, heads = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, \
        cfg.num_attention_heads
    assert [t.shape for t in got] == [
        (SEQ, cfg.q_lora_rank), (SEQ, heads, 128), (SEQ, heads, 128),
        (SEQ, heads, cfg.v_head_dim)]
    np.testing.assert_allclose(got[0], want[0], atol=1e-6)
    for mine, plain in zip(got[1:3], want[1:3]):
        np.testing.assert_allclose(mine[..., :d], plain, atol=2e-6)
        assert not np.asarray(mine[..., d:]).any()
    np.testing.assert_allclose(got[3], want[3], atol=2e-6)


def test_mla_weights_read_no_input_and_keep_the_published_tree():
    """What ``mla_qkv`` multiplies by is a function of the attention
    leaves alone (the jax filter runs it once per load), laid out with
    the latent dimension last; the tree ``init_params`` makes, which
    the benchmark's seeds and references share, holds the published
    shapes."""
    cfg = _cfg()
    a = longcat.init_params(
        cfg, jax.random.PRNGKey(0))["layers"][0]["sub"][0]["attn"]
    assert a["wq_b"].shape == (32, 4 * 16)
    assert a["wkv_b"].shape == (16, 4 * (12 + 16))
    wq, wk, wv = latent.mla_weights(a, cfg)
    assert (wq.shape, wk.shape, wv.shape) \
        == ((4, 128, 32), (4, 128, 16), (4, 16, 16))
    w = np.asarray(a["wkv_b"]).reshape(16, 4, 28)
    np.testing.assert_array_equal(wq[:, :16], np.asarray(
        a["wq_b"]).reshape(32, 4, 16).transpose(1, 2, 0))
    np.testing.assert_array_equal(wk[:, :12], w[..., :12].transpose(1, 2, 0))
    np.testing.assert_array_equal(wv, w[..., 12:].transpose(1, 2, 0))
    assert not np.asarray(wq[:, 16:]).any()
    assert not np.asarray(wk[:, 12:]).any()


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", sorted(DECODERS))
def test_forward_is_the_unpadded_forward(monkeypatch, name, seed):
    """The whole scoring pass with a head padded equals the pass with
    the projections in their plain form (the program before PR 33) in
    float32: zero columns add zero to a score, and the score's scale
    is the configuration's ``nope + rope``, not the padded width."""
    model, make, _ = DECODERS[name]
    monkeypatch.setattr(model, "BLOCK_Q", 16)
    cfg = make()
    params = model.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _tokens(20 + seed)

    def run():
        out = jax.jit(lambda p, t: model.forward(p, t[None], cfg))(
            params, tokens)
        return [np.asarray(t) for t in out]

    got = run()
    monkeypatch.setattr(model, "mla_qkv", _plain_mla_qkv)
    want = run()
    spread = float(np.ptp(want[0]))
    np.testing.assert_allclose(got[0], want[0], atol=1e-6 * max(spread, 1))
    np.testing.assert_allclose(got[1], want[1], atol=1e-6 * max(spread, 1))
    np.testing.assert_array_equal(got[2], want[2])


def _equations(jaxpr):
    """Every equation of ``jaxpr``, sub-programs' included (``jnp.pad``
    is a ``jit`` of its own), a Pallas kernel's body apart."""
    from nnstreamer_tpu.filters import prepare
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in prepare.sub_jaxprs(eqn):
                yield from _equations(sub)


@pytest.mark.parametrize("name", sorted(DECODERS))
def test_no_activation_is_padded_to_the_lane_width(monkeypatch, name):
    """Read off the jaxpr, as ``kernel_calls`` is: with values a lane
    multiple wide (as both cells have them) the traced program pads no
    ``[H, S, D]`` array's columns on its way to the kernel; the plain
    form pads q and k in every attention."""
    model, make, _ = DECODERS[name]
    cfg = make(v_head_dim=128)
    shapes = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))

    def widened():
        closed = jax.make_jaxpr(lambda p, t: model.forward(p, t[None], cfg))(
            shapes, jax.ShapeDtypeStruct((SEQ,), jnp.int32))
        return [e for e in _equations(closed.jaxpr)
                if e.primitive.name == "pad"
                and e.invars[0].aval.shape[:2] == (cfg.num_attention_heads,
                                                   SEQ)
                and e.outvars[0].aval.shape[2] > e.invars[0].aval.shape[2]]

    assert widened() == []
    monkeypatch.setattr(model, "mla_qkv", _plain_mla_qkv)
    attentions = 4 if name == "longcat" else cfg.num_hidden_layers
    assert len(widened()) == 2 * attentions
