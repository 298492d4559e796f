"""``models/dots3_note.py`` (full latent attention under the indexer and
sliding-window latent attention of other sizes in one stack, a headwise
gate on both, the sigmoid router over a share of the experts) at a tiny
size on the CPU with seeded weights, against the benchmark's plain
reference (``benchmark/refs/dots3_note.py``, which imports nothing of
the program) and against hand-worked values."""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.models import dots3_note, glm_dsa, latent, zoo
from nnstreamer_tpu.ops.sparse_attention import blocked_causal_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
from refs import dots3_note as ref  # noqa: E402

FULL, SLIDING = dots3_note.FULL, dots3_note.SLIDING
# the configuration's rehearsal sizes (benchmark/configs/dots3_ep8_l5.json)
# and its five layers as layer_types[0:5] spells them: at 64 tokens a
# window of 17 and an index_topk of 16 both drop keys, the two kinds
# have heads, ranks and head sizes of their own
SIZES = dict(
    vocab_size=64, hidden_size=64, num_hidden_layers=5,
    first_k_dense_replace=1,
    layer_types=[FULL, FULL, SLIDING, SLIDING, SLIDING],
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
    rope_theta=1e6, index_n_heads=2, index_head_dim=8, index_topk=16,
    swa_num_attention_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=32,
    swa_qk_nope_head_dim=20, swa_qk_rope_head_dim=4, swa_v_head_dim=16,
    swa_rope_theta=1e4, sliding_window_size=17,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True, intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=32, num_experts_per_tok=4,
    n_shared_experts=1, routed_scaling_factor=1.0, rms_norm_eps=1e-5)
SEQ = 64
RANK1 = dict(SIZES, expert_rank=1)      # the reference's experts 8..15


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Several attention blocks and several expert tiles at this size."""
    monkeypatch.setattr(dots3_note, "BLOCK_Q", 16)
    monkeypatch.setattr(latent, "EXPERT_TILE", 8)


def _cfg(dtype=jnp.float32, **over):
    share = dict(held_first=8, held_count=8, dtype=dtype)
    share.update(over)
    return dots3_note.Dots3NoteConfig.from_hf(SIZES, **share)


def _tokens(seed, n=SEQ):
    return np.random.default_rng(seed).integers(0, SIZES["vocab_size"], n,
                                                np.int32)


# one compiled program a configuration, whichever test asks
_forward = jax.jit(dots3_note.forward, static_argnums=2)


def _run(cfg, params, tokens):
    out = _forward(params, jnp.asarray(tokens)[None], cfg)
    return np.asarray(out[0][0]), np.asarray(out[1][0]), np.asarray(out[2])


def _hidden(seed, cfg):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (SEQ, cfg.hidden_size), jnp.float32)


# float32: the two sides differ in the order of their sums alone, which
# measured at most 1.3e-6 of the logits' range and 6e-6 in a
# log-probability over six seeds: the tolerances stand 8x and 17x over.
# bfloat16: an activation carries 8 bits through five layers, and at
# this size one key moved across the 16th place or one expert across the
# 4th of a router 32 wide is a sixteenth of a token's attention or a
# quarter of its routed part; measured 0.07-0.22 of the logits' range,
# 0.45-1.48 in a log-probability and 16-21 of the ~260 pairs the four
# loads count over six seeds: the tolerances stand about 1.4x over (the
# float32 case is the one that holds the mathematics)
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,logit_tol,logprob_tol,load_tol", [
    (jnp.float32, 1e-5, 1e-4, 0), (jnp.bfloat16, 0.3, 2.0, 28)],
    ids=["float32", "bfloat16"])
def test_program_against_plain_reference(seed, dtype, logit_tol,
                                         logprob_tol, load_tol):
    cfg = _cfg(dtype)
    params = dots3_note.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = _tokens(seed + 10)
    last, logprobs, load = _run(cfg, params, tokens)
    want = ref.forward(params, tokens, RANK1, "f32")
    assert load.shape == want[2].shape == (4, 8)
    assert np.abs(last - want[0]).max() \
        <= logit_tol * (want[0].max() - want[0].min())
    assert np.abs(logprobs - want[1]).max() <= logprob_tol
    assert logprobs[-1] == 0 and (logprobs[:-1] < 0).all()
    assert np.abs(load - want[2]).sum() <= load_tol


def test_the_headwise_gate_is_one_scalar_a_head():
    """A layer's attention half is ``h + sum_h sigmoid(x W_g)[:, h] *
    (P v)_h W_o[h]``: the heads' outputs written out one at a time, each
    times its own scalar; and the gate is live (a gate of zeros, 0.5 on
    every head, gives another result)."""
    cfg = _cfg()

    @jax.jit
    def parts(h, layer, zero):
        # the layer's result, and the heads' outputs to write it out from
        kind = FULL if "indexer" in layer else SLIDING
        sizes = cfg.full if kind == FULL else cfg.sliding
        a = layer["attn"]
        x = latent.rmsnorm(h, layer["attn_norm"], cfg.rms_norm_eps)
        pos = jnp.arange(SEQ)
        q_scale, kv_scale = cfg.lora_scales(sizes)
        c_q, q, k, v = latent.mla_qkv(x, a, pos, sizes, q_scale=q_scale,
                                      kv_scale=kv_scale)
        mask = None if kind == SLIDING else glm_dsa.selection(
            layer["indexer"], sizes, x, c_q, pos)
        o = blocked_causal_attention(
            q, k, v, block_q=16, key_mask=mask,
            window=cfg.sliding_window_size if kind == SLIDING else None,
            scale=(sizes.qk_nope_head_dim + sizes.qk_rope_head_dim) ** -0.5)
        return (dots3_note.attend(h, layer, kind, cfg),
                dots3_note.attend(h, zero, kind, cfg),
                o, jax.nn.sigmoid(x @ a["wg"]))

    params = dots3_note.init_params(cfg, jax.random.PRNGKey(4))
    h = _hidden(5, cfg)
    for layer, sizes in ((params["layers"][1], cfg.full),
                         (params["layers"][2], cfg.sliding)):
        a = layer["attn"]
        zero = dict(layer, attn=dict(a, wg=jnp.zeros_like(a["wg"])))
        got, half, o, gate = parts(h, layer, zero)
        heads, vd = sizes.num_attention_heads, sizes.v_head_dim
        wo = a["wo"].reshape(heads, vd, -1)
        want = h + sum(gate[:, j:j + 1] * (o[:, j] @ wo[j])
                       for j in range(heads))
        np.testing.assert_allclose(got, want, atol=2e-5)
        np.testing.assert_allclose(
            half, h + sum(0.5 * (o[:, j] @ wo[j]) for j in range(heads)),
            atol=2e-5)
        assert float(jnp.abs(half - got).max()) > 1e-2


def test_each_layer_gets_its_kinds_attention(monkeypatch):
    """The full layers call the indexer and no window, the sliding ones
    the window of ``sliding_window_size`` and no indexer, each at its
    own kind's heads and sizes; a sliding layer's weights hold no
    indexer."""
    cfg = _cfg()
    params = dots3_note.init_params(cfg, jax.random.PRNGKey(0))
    assert ["indexer" in layer for layer in params["layers"]] \
        == [True, True, False, False, False]
    seen = []
    real_out, real_sel = latent.causal_attention_out, glm_dsa.selection

    def out(q, k, v, wo, **kw):
        seen.append(("attend", q.shape[1:], v.shape[2], kw["window"],
                     kw["key_mask"] is not None, kw["scope"]))
        return real_out(q, k, v, wo, **kw)

    def selection(ix, sizes, x, c_q, positions):
        seen.append(("indexer", c_q.shape[1], sizes.index_topk))
        return real_sel(ix, sizes, x, c_q, positions)

    monkeypatch.setattr(latent, "causal_attention_out", out)
    monkeypatch.setattr(glm_dsa, "selection", selection)
    # traced, not run: the calls are made while the program is built
    jax.eval_shape(lambda p, t: dots3_note.forward(p, t, cfg), params,
                   jnp.asarray(_tokens(0))[None])
    # q [S, heads, nope + rope padded to the lanes]
    full = [("indexer", 32, 16),
            ("attend", (4, 128), 16, None, True, "block/attn/full")]
    window = [("attend", (2, 128), 16, 17, False, "block/attn/window")]
    assert seen == full * 2 + window * 3


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_shares_add_up(seed):
    """The routed parts of all four shares of 8 of the 32 experts (the
    tile loops) plus the shared expert once are the uncut layer's output
    (the kernel's path, the whole router held), and the shares' loads
    side by side are the uncut layer's load: every token-expert pair is
    served by exactly one share."""
    full = _cfg(held_first=0, held_count=0)
    layer = dots3_note.init_params(full, jax.random.PRNGKey(seed))[
        "layers"][3]
    h = _hidden(seed + 20, full)

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


def test_config_reads_the_published_keys():
    """The configuration file's keys, as the catalog's ``config`` spells
    them, give each kind its own sizes; ``layer_types`` must name a kind
    for each layer."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots3_ep8_l5.json")) as f:
        hf = json.load(f)
    cfg = dots3_note.Dots3NoteConfig.from_hf(
        dict(hf, n_routed_experts=256), held_first=0, held_count=32)
    assert cfg.layer_types == (FULL, FULL, SLIDING, SLIDING, SLIDING)
    assert (cfg.held, cfg.n_moe_layers, cfg.sliding_window_size) \
        == (32, 4, 513)
    assert cfg.full == dots3_note.LatentSizes(
        128, 1024, 512, 128, 64, 128, 8e7, 1e-5, 64, 128, 2048)
    assert cfg.sliding == dots3_note.LatentSizes(
        64, 1024, 1024, 192, 64, 128, 5e4, 1e-5)
    np.testing.assert_allclose(cfg.lora_scales(cfg.full),
                               [5 ** 0.5, 10 ** 0.5])
    np.testing.assert_allclose(cfg.lora_scales(cfg.sliding), [5 ** 0.5] * 2)
    with pytest.raises(ValueError, match="layer_types"):
        dots3_note.Dots3NoteConfig.from_hf(dict(SIZES, num_hidden_layers=4))
    with pytest.raises(ValueError, match="layer_types"):
        dots3_note.Dots3NoteConfig.from_hf(
            dict(SIZES, layer_types=[FULL] * 4 + ["chunked_attention"]))
    with pytest.raises(ValueError, match="headwise"):
        dots3_note.Dots3NoteConfig.from_hf(
            dict(SIZES, swa_attention_gate_type="elementwise"))
    with pytest.raises(ValueError, match="rescaled"):
        dots3_note.Dots3NoteConfig.from_hf(
            dict(SIZES, apply_mla_qkv_lora_rescale=False))
    with pytest.raises(ValueError, match="outside"):
        dots3_note.Dots3NoteConfig.from_hf(SIZES, held_first=30,
                                           held_count=8)
    with pytest.raises(ValueError, match="unknown option"):
        zoo.build("dots3_note", hidden="64")


def test_pipeline_gives_the_direct_calls_three_tensors():
    uri = "zoo://dots3_note?seq=64&held_first=8&held_count=8&seed=3"
    apply_fn, params, in_info, out_info = zoo.build(
        "dots3_note", seq="64", held_first="8", held_count="8", seed="3")
    assert [tuple(i.shape) for i in out_info] == [(64,), (64,), (4, 8)]
    frames = [_tokens(i) for i in range(3)]
    want = [jax.jit(apply_fn)(params, f) for f in frames]
    caps = ("other/tensors,format=static,num_tensors=1,types=(string)int32,"
            "dimensions=(string)64,framerate=0/1")
    p = parse_launch(f'appsrc name=in caps="{caps}" ! tensor_filter name=f '
                     f'framework=jax model={uri} in-flight=2 '
                     f'prefetch-host=true ! appsink name=out')
    p.start()
    for f in frames:
        p["in"].push_buffer(Buffer.from_arrays([f]))
    p["in"].end_stream()
    assert p.wait_eos(timeout=120)
    got = [[np.asarray(c.host()) for c in b.chunks] for b in p["out"].buffers]
    report = p["f"].transfer_report()
    p.stop()
    # 5 layers x 4 blocks of 16 queries (the fixture's BLOCK_Q); 8 of 32
    # experts held: the split form's two kernels twice in each of the 4
    # expert layers (ops/grouped.py::takes_split)
    assert report["kernel_calls"] == {"nns_masked_attention": 20,
                                      "nns_grouped_gate_up": 8,
                                      "nns_grouped_down": 8}
    assert report.get("prepared_leaves", 0) == 0
    assert len(got) == 3
    for g, w in zip(got, want):
        assert [x.dtype for x in g] == [np.float32, np.float32, np.int32]
        for a, b in zip(g, w):
            np.testing.assert_array_equal(a, np.asarray(b))
