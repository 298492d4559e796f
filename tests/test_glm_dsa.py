"""``models/glm_dsa.py`` (latent attention, the sparse-attention indexer,
the sigmoid router over a share of the experts) and the ops under it,
at a tiny size on the CPU with seeded weights, against the benchmark's
plain reference (``benchmark/refs/glm_dsa.py``, which imports nothing of
the program) and against hand-worked values."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.models import glm_dsa, latent, zoo
from nnstreamer_tpu.ops import sparse_attention
from nnstreamer_tpu.ops.grouped import group_by_expert, grouped_swiglu
from nnstreamer_tpu.ops.sparse_attention import (
    blocked_causal_attention, reference_blocked_attention, topk_mask)

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
    monkeypatch.setattr(glm_dsa, "EXPERT_TILE", 8)


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
    x = glm_dsa.rmsnorm(h, layer["attn_norm"], cfg.rms_norm_eps)
    c_q, _, _, _ = glm_dsa.mla_qkv(x, layer["attn"], pos, cfg)
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
    _, q, k, v = glm_dsa.mla_qkv(x, a, pos, cfg)
    got = blocked_causal_attention(q, k, v, scale=16 ** -0.5,
                                   block_q=block_q)
    nope, rp, vd, r = 12, 4, 16, cfg.kv_lora_rank
    kv = x @ a["wkv_a"]
    c_kv = glm_dsa.rmsnorm(kv[:, :r], a["kv_norm"], cfg.rms_norm_eps)
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
    once are the uncut layer's output, and the ranks' loads side by
    side are the uncut layer's load: every token-expert pair is served
    by exactly one rank."""
    full = _cfg(held_first=0, held_count=0)
    layer = glm_dsa.init_params(full, jax.random.PRNGKey(seed))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(seed + 20),
                          (SEQ, full.hidden_size), jnp.float32)
    whole, load = glm_dsa.moe_ffn(h, layer, full)
    shared = glm_dsa.swiglu(
        glm_dsa.rmsnorm(h, layer["ffn_norm"], full.rms_norm_eps),
        layer["moe"]["shared"])
    total, loads = h + shared, []
    for rank in range(4):
        cfg = _cfg(held_first=8 * rank, held_count=8)
        part = dict(layer, moe=dict(layer["moe"], experts=jax.tree.map(
            lambda w: w[8 * rank:8 * rank + 8], layer["moe"]["experts"])))
        out, load_r = glm_dsa.moe_ffn(h, part, cfg)
        total = total + (out - h - shared)
        loads.append(np.asarray(load_r))
    np.testing.assert_allclose(total, whole, atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(loads), load)
    assert int(load.sum()) == SEQ * full.num_experts_per_tok


@pytest.mark.parametrize("tile", [1, 8, 64])
@pytest.mark.parametrize("held_first,held", [(0, 6), (3, 2), (0, 1)])
def test_grouped_swiglu_is_exact(tile, held_first, held):
    """Against every expert applied to every token and masked: no pair
    of a held expert dropped, none of another expert served, an expert
    nobody chose costs nothing and breaks nothing."""
    rng = np.random.default_rng(tile + held)
    t, d, f, k, router = 40, 16, 24, 3, 7          # expert 6 is never chosen
    x = rng.standard_normal((t, d)).astype(np.float32)
    choice = np.stack([rng.permutation(6)[:k] for _ in range(t)]
                      ).astype(np.int32)
    weight = rng.random((t, k)).astype(np.float32)
    w1, w3 = (rng.standard_normal((router, d, f)).astype(np.float32)
              for _ in range(2))
    w2 = rng.standard_normal((router, f, d)).astype(np.float32)
    sl = slice(held_first, held_first + held)
    order, counts = group_by_expert(jnp.asarray(choice), held_first, held)
    got = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile))(
        x, order, counts, weight, w1[sl], w3[sl], w2[sl])
    want = np.zeros((t, d))
    for e in range(held_first, held_first + held):
        y = (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        want += np.asarray(y) * (weight * (choice == e)).sum(-1)[:, None]
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-4)
    assert counts.tolist() == [(choice == e).sum()
                               for e in range(held_first, held_first + held)]


def _pairs_per_expert(routing, rng, pairs, tile, router):
    """How many of ``pairs`` pairs each of ``router`` experts serves,
    for the routings a walk over row tiles can get wrong."""
    if routing == "one_expert":
        return np.bincount([3], minlength=router) * pairs
    counts = rng.multinomial(pairs, np.full(router, 1 / router))
    gaps = {"gap_first": [0], "gap_last": [router - 1],
            "gap_middle": [5, 6]}.get(routing, [])
    if routing == "many_tiles":
        # expert 2 serves more than three tiles, from inside a tile on
        counts = np.full(router, (pairs - 3 * tile - 5) // (router - 1))
        counts[2] = 3 * tile + 5
    if routing == "off_tile":
        # no expert's first row is a tile's first but expert 0's
        ends = np.arange(1, router) * (pairs // router)
        ends += ends % tile == 0
        counts = np.diff(np.concatenate([[0], ends, [pairs]]))
        assert (np.cumsum(counts)[:-1] % tile).all()
    counts[gaps] = 0
    counts[1] += pairs - counts.sum()
    assert counts.sum() == pairs and (counts >= 0).all()
    return counts


@pytest.mark.parametrize("whole", [True, False], ids=["sorted", "loops"])
@pytest.mark.parametrize("tile,dtype,tol", [(8, jnp.float32, 1e-3),
                                            (32, jnp.float32, 1e-3),
                                            (16, jnp.bfloat16, 0.15)])
@pytest.mark.parametrize("routing", [
    "gap_middle", "gap_first", "gap_last", "many_tiles", "off_tile",
    "one_expert", "ragged_end"])
def test_grouped_swiglu_holds_the_whole_router(routing, tile, dtype, tol,
                                               whole):
    """``held_count`` equal to the router's width: every one of the ``T
    x K`` pairs is served here, none goes to the tail, and the result is
    the dense form's (every expert over every token, weighted by the
    token's weight for it or 0), from the tile loops and from the
    sorted form (the router's width named: the rows gathered once, the kernel
    ``nns_grouped_swiglu`` through the interpreter, gathered back)
    alike. The routings are those a walk over row tiles can get wrong:
    experts that serve nobody first, last and two in the middle, an
    expert over more than three tiles, every boundary inside a tile,
    all pairs on one expert, ``T x K`` no multiple of the tile."""
    rng = np.random.default_rng(tile)
    t, d, f, k, router = 97 if routing == "ragged_end" else 96, 16, 24, 4, 12
    x = rng.standard_normal((t, d)).astype(np.float32)
    counts_want = _pairs_per_expert(routing, rng, t * k, tile, router)
    choice = rng.permutation(np.repeat(np.arange(router), counts_want)
                             ).reshape(t, k).astype(np.int32)
    weight = rng.random((t, k)).astype(np.float32)
    w1, w3 = (rng.standard_normal((router, d, f)).astype(np.float32)
              for _ in range(2))
    w2 = rng.standard_normal((router, f, d)).astype(np.float32)
    order, counts = group_by_expert(jnp.asarray(choice), 0, router)
    assert counts.tolist() == counts_want.tolist()
    assert sorted(np.asarray(order).tolist()) == list(range(t * k))
    got = jax.jit(lambda *a: grouped_swiglu(
        *a, tile=tile, router=router if whole else 0))(
        *(jnp.asarray(a, dtype) for a in (x,)), order, counts, weight,
        *(jnp.asarray(a, dtype) for a in (w1, w3, w2)))
    assert got.dtype == jnp.float32
    dense = np.zeros((t, router), np.float32)
    np.add.at(dense, (np.arange(t)[:, None], choice), weight)
    every = jnp.einsum("tef,efd->ted", jax.nn.silu(
        jnp.einsum("td,edf->tef", x, w1)) * jnp.einsum("td,edf->tef", x, w3),
        w2)
    want = jnp.einsum("ted,te->td", every, dense)
    np.testing.assert_allclose(got, want, atol=tol * (1 + 9 * (
        dtype == jnp.bfloat16)), rtol=tol)


@pytest.mark.parametrize("counts", [
    [0, 5, 3, 9, 1, 14],            # nobody chose the first expert
    [5, 3, 0, 0, 30, 1, 1, 0, 8],   # nor two in the middle, then one
    [7, 17, 4, 0],                  # nor the last
    [8, 9, 16, 1],                  # exactly a tile, a row more, two tiles
    [3, 1, 7, 2, 5, 6],             # every expert under a tile
    [0, 0, 41, 0],                  # all pairs on one expert
    [8, 8, 8],                      # every boundary a tile's already
    [0, 5, 3, 0, 0, 30, 1, 1, 0, 8, 0],
], ids=["gap_first", "gap_middle", "gap_last", "tile_and_one_more",
        "all_under_a_tile", "one_expert", "aligned_already", "pr35s"])
def test_grouped_kernel_walks_an_expert_a_tile(counts):
    """The kernel's grid (``_walk``) over the two layouts
    (``_spread``): a step is a tile and a tile has one expert; an
    expert's tiles are consecutive, ``ceil(count / tile)`` of them,
    nobody's expert has none; ``tiles_walked`` is the count of live
    steps; the steps past them repeat the last live one (its expert,
    its rows in, and its block out, ``min(i, live - 1)``), so nothing
    is fetched for them. On the way in every expert's run starts on a
    multiple of ``align`` rows and a step reads ``tile`` rows from its
    expert's ``j``-th tile on, inside the buffer; on the way out every
    sorted row sits once, at row ``j`` of its expert's first tile on;
    the free slots are all the others."""
    from nnstreamer_tpu.ops.grouped import _spread, _walk, tiles_walked
    tile, align, rows, g = 8, 2, sum(counts), len(counts)
    tiles = -(-rows // tile) + g - 1
    expert, live, row = (np.asarray(a) for a in _walk(
        jnp.asarray(counts, jnp.int32), tiles, tile, align))
    assert expert.shape == row.shape == (tiles,)
    of = [-(-c // tile) for c in counts]
    assert live.tolist() == [sum(of)] == [tiles_walked(np.asarray(counts),
                                                       tile)]
    assert int(tiles_walked(jnp.asarray(counts), tile)) == sum(of) <= tiles
    live = int(live[0])
    assert expert[:live].tolist() == np.repeat(np.arange(g), of).tolist()
    assert set(expert[live:].tolist()) <= {int(expert[live - 1])}
    assert set(row[live:].tolist()) <= {int(row[live - 1])}
    # the way in: runs from multiples of ``align``, a tile of slack
    slots = rows + g * (align - 1) + tile
    to, gaps = (np.asarray(a) for a in _spread(
        jnp.asarray(counts, jnp.int32), rows, align, slots))
    held = [-(-c // align) * align for c in counts]
    run = np.cumsum(held) - held
    assert to.tolist() == [run[e] + j for e, c in enumerate(counts)
                           for j in range(c)]
    assert gaps.tolist() == sorted(set(range(slots)) - set(to.tolist()))
    assert row[:live].tolist() == [run[e] + j * tile
                                   for e, n in enumerate(of)
                                   for j in range(n)]
    assert (row % align == 0).all() and row.max() + tile <= slots
    # the way out: every pair served once, on a tile of its own expert
    to, gaps = (np.asarray(a) for a in _spread(
        jnp.asarray(counts, jnp.int32), rows, tile, tiles * tile))
    first = np.cumsum(of) - of
    assert to.tolist() == [first[e] * tile + j for e, c in enumerate(counts)
                           for j in range(c)]
    assert expert[to // tile].tolist() == np.repeat(np.arange(g),
                                                    counts).tolist()
    assert gaps.tolist() == sorted(set(range(tiles * tile)) - set(to.tolist()))


def _whole_router_case(rng, dtype, t=48, k=2, router=6, d=16, f=24):
    """Operands on which every order of accumulation gives the same
    bits: ``x``, ``w1``, ``w3`` small integers (gate and up are exact),
    each column of an expert's ``w2`` one signed power of two (the last
    product's sums have one term), so two statements of one product
    differ only where they serve a row otherwise."""
    x = rng.integers(-2, 3, (t, d)).astype(np.float32)
    choice = np.stack([rng.permutation(router)[:k] for _ in range(t)]
                      ).astype(np.int32)
    weight = rng.random((t, k)).astype(np.float32)
    w1, w3 = (rng.integers(-2, 3, (router, d, f)).astype(np.float32)
              for _ in range(2))
    w2 = np.zeros((router, f, d), np.float32)
    for e in range(router):
        w2[e, rng.integers(0, f, d), np.arange(d)] = \
            rng.choice([-2., -1., -.5, .5, 1., 2.], d)
    order, counts = group_by_expert(jnp.asarray(choice), 0, router)
    return (jnp.asarray(x, dtype), order, counts, jnp.asarray(weight),
            *(jnp.asarray(w, dtype) for w in (w1, w3, w2)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_grouped_kernel_is_three_ragged_dots_to_the_bit(dtype):
    """The module's docstring: the ``whole`` form's result is that of
    three ``jax.lax.ragged_dot`` s on the buffer sorted by expert, with
    ``silu(gate) * up`` rounded once between them, bit for bit (on
    operands that leave the order of accumulation no say, which the
    CPU's two products do not share; on the chip it was read on the
    cell's own, PERF.md)."""
    args = _whole_router_case(np.random.default_rng(5), dtype)
    t, k = args[3].shape

    def ragged(x, order, counts, weight, w1, w3, w2):
        xs = x[order // k]
        gate, up = (jax.lax.ragged_dot(
            xs, w, counts, preferred_element_type=jnp.float32)
            for w in (w1, w3))
        y = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(x.dtype), w2,
                               counts, preferred_element_type=jnp.float32)
        y = y[jnp.argsort(order)].reshape(t, k, -1)
        return jnp.sum(y * weight[:, :, None], axis=1)

    got = jax.jit(lambda *a: grouped_swiglu(*a, tile=8, router=6))(*args)
    want = np.asarray(jax.jit(ragged)(*args))
    assert np.abs(want).max() > 1
    np.testing.assert_array_equal(np.asarray(got), want)


def test_grouped_kernel_reads_no_row_of_a_tiles_tail():
    """A step multiplies a whole tile: past its expert's rows the
    slots left free by the alignment (they hold pair 0's row, a real
    one) and the next experts' rows, on the wrong matrices, stored
    where nobody reads. With pair 0's token poisoned (NaN), every other
    token's result is what it was, bit for bit, though the tiles hold
    the NaN row many times over."""
    from nnstreamer_tpu.ops.grouped import tiles_walked
    x, order, counts, *rest = _whole_router_case(np.random.default_rng(9),
                                                 jnp.float32)
    t, k, tile = x.shape[0], order.shape[0] // x.shape[0], 8
    # more than a tile's worth of tail rows in the live tiles
    assert int(tiles_walked(counts, tile)) * tile - t * k >= 8
    run = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile, router=6))
    clean = np.asarray(run(x, order, counts, *rest))
    dirty = np.asarray(run(x.at[0].set(jnp.nan), order, counts, *rest))
    assert np.isnan(dirty[0]).all() and np.isfinite(clean).all()
    np.testing.assert_array_equal(dirty[1:], clean[1:])


def _choice(case, rng, t, k, tile, held_first, held, router):
    """``[t, k]`` expert ids, a token's all different, for a held share
    ``[held_first, held_first + held)`` of ``router`` experts."""
    inside = np.arange(held_first, held_first + held)
    outside = np.setdiff1d(np.arange(router), inside)
    if case == "none_held":
        return np.stack([rng.permutation(outside)[:k] for _ in range(t)])
    if case == "one_expert":                   # k == 1: every pair on it
        return np.full((t, k), held_first + 1)
    if case == "half_full":
        # k == 1; last tiles half full to the row, one row over, whole,
        # and a single row: both sides of where the two loops part
        half = tile // 2
        pairs = [2 * tile + half, tile + half + 1, tile, 1]
        ids = np.repeat(inside, pairs)
        ids = np.concatenate([ids, np.full(t - len(ids), outside[0])])
        return rng.permutation(ids)[:, None]
    choice = np.stack([rng.permutation(router)[:k] for _ in range(t)])
    if case == "several_tiles":
        # the first held expert serves most tokens: several tiles of it
        rows = rng.permutation(t)[:t * 5 // 6]
        choice[rows] = np.where(choice[rows] == held_first,
                                outside[0], choice[rows])
        choice[rows, 0] = held_first
    if case == "gap":
        # the second held expert serves nobody, its neighbours do
        choice = np.where(choice == held_first + 1, outside[0], choice)
    return choice


@pytest.mark.parametrize("case,t,k,tile,dtype,tol", [
    ("several_tiles", 48, 2, 8, jnp.float32, 1e-5),
    ("gap", 48, 2, 8, jnp.float32, 1e-5),
    ("one_expert", 40, 1, 16, jnp.float32, 1e-5),
    ("none_held", 24, 2, 8, jnp.float32, 0.0),
    ("half_full", 64, 1, 8, jnp.float32, 1e-5),
    # an odd tile's half is rounded down: 5 rows and 2
    ("odd_tile", 48, 2, 5, jnp.float32, 1e-5),
    # silu(gate) * up reaches the last product in bfloat16
    ("bfloat16", 64, 2, 16, jnp.bfloat16, 2e-2),
    ("bfloat16_half_full", 96, 1, 16, jnp.bfloat16, 2e-2),
])
def test_grouped_swiglu_serves_every_routing(case, t, k, tile, dtype, tol):
    """Both loops of :func:`grouped_swiglu` (whole tiles, and last tiles
    at half the rows) against each pair computed alone: an expert of
    several tiles, one that serves nobody between two that do, every
    pair on one expert, no pair of a held expert (exactly 0), last
    tiles on both sides of half full, an odd tile, bfloat16."""
    held_first, held, router, d, f = 2, 4, 9, 16, 24
    rng = np.random.default_rng(len(case))
    case = case.removeprefix("bfloat16_")
    choice = _choice(case, rng, t, k, tile, held_first, held, router)
    x = jnp.asarray(rng.standard_normal((t, d)), dtype)
    weight = jnp.asarray(rng.random((t, k)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((held, d, f)) * d ** -0.5,
                          dtype) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held, f, d)) * f ** -0.5, dtype)
    order, counts = group_by_expert(jnp.asarray(choice, jnp.int32),
                                    held_first, held)
    expected = {"several_tiles": counts[0] > 2 * tile,
                "gap": (counts[1] == 0) & (counts[0] > 0) & (counts[2] > 0),
                "one_expert": counts[1] == t * k,
                "none_held": counts.sum() == 0,
                "half_full": (counts % tile == jnp.asarray(
                    [tile // 2, tile // 2 + 1, 0, 1])).all()}.get(case, True)
    assert bool(expected), counts
    got = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile))(
        x, order, counts, weight, w1, w3, w2)
    assert got.shape == (t, d) and got.dtype == jnp.float32
    want = np.zeros((t, d), np.float32)
    for tok, slot in np.argwhere((choice >= held_first)
                                 & (choice < held_first + held)):
        e = choice[tok, slot] - held_first
        gate, up = (jnp.dot(x[tok], w[e], preferred_element_type=jnp.float32)
                    for w in (w1, w3))
        want[tok] += weight[tok, slot] * np.asarray(jnp.dot(
            (jax.nn.silu(gate) * up).astype(dtype), w2[e],
            preferred_element_type=jnp.float32))
    got = np.asarray(got)
    if case == "none_held":
        assert not got.any()
    else:
        assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=tol * max(np.abs(want).max(),
                                                         1.0))


@pytest.mark.parametrize("block_q", [8, 32])
def test_blocked_attention_honours_a_key_mask(block_q):
    rng = np.random.default_rng(1)
    s, h, d = 32, 2, 8
    q, k, v = (rng.standard_normal((s, h, d)).astype(np.float32)
               for _ in range(3))
    keep = rng.random((s, s)) < 0.5
    keep |= np.eye(s, dtype=bool)
    calls = []

    def key_mask(lo, hi):
        calls.append((lo, hi))
        return None if lo == 0 else jnp.asarray(keep[lo:hi, :hi])

    got = blocked_causal_attention(q, k, v, scale=0.5, block_q=block_q,
                                   key_mask=key_mask)
    mask = np.tril(np.ones((s, s), bool))
    mask[block_q:] &= keep[block_q:]
    scores = np.einsum("qhd,khd->hqk", q, k) * 0.5
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
    np.testing.assert_allclose(got, np.einsum("hqk,khd->qhd", probs, v),
                               atol=1e-5)
    assert calls == [(lo, lo + block_q) for lo in range(0, s, block_q)]


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


def _keep(case, s, block_q, tile_k, rng):
    """The ``[S, S]`` selection of a kernel case (True = keep; the
    causal rule is the function's own), None for a causal-only one."""
    rows, cols = np.arange(s)[:, None], np.arange(s)[None, :]
    if case in ("causal", "bfloat16_causal"):
        return None
    if case == "single_key":
        return cols == rng.integers(0, rows + 1)
    keep = rng.random((s, s)) < 0.5
    if case == "tile_dropped":
        # rows of the later blocks lose every key of the first key tile,
        # every third row every key of its last whole tile as well
        keep[block_q:, :tile_k] = False
        last = np.maximum(rows // tile_k - 1, 0) * tile_k
        keep &= ~((rows % 3 == 0) & (rows >= 2 * tile_k)
                  & (cols >= last) & (cols < last + tile_k))
    return keep | (cols == rows)


@pytest.mark.parametrize("case,s,block_q,tile_q,dtype,tol", [
    ("causal", 256, 64, 64, jnp.float32, 2e-5),
    ("masked", 256, 64, 32, jnp.float32, 2e-5),
    # S a multiple of neither tile, block_q not one of the key tile
    ("masked_ragged", 300, 96, 32, jnp.float32, 2e-5),
    ("tile_dropped", 384, 128, 64, jnp.float32, 2e-5),
    ("single_key", 256, 128, 128, jnp.float32, 2e-5),
    # the weights reach the second product in bfloat16, as the oracle's
    ("bfloat16", 256, 64, 64, jnp.bfloat16, 2e-2),
    ("bfloat16_causal", 200, 64, 64, jnp.bfloat16, 2e-2),
])
def test_masked_attention_kernel_is_the_plain_block(monkeypatch, case, s,
                                                    block_q, tile_q, dtype,
                                                    tol):
    """``nns_masked_attention`` (the same body the chip compiles, here
    through the Pallas interpreter) against the block in plain XLA,
    several key tiles a query tile: causal-only and masked blocks, a row
    that keeps nothing of a whole key tile (no NaN, same result), one
    key a row, ragged sizes; ``key_mask`` once a block, in order."""
    tile_k = 128
    monkeypatch.setattr(sparse_attention, "TILE_Q", tile_q)
    monkeypatch.setattr(sparse_attention, "TILE_K", tile_k)
    rng = np.random.default_rng(len(case))
    q, k = (jnp.asarray(rng.standard_normal((s, 2, 24)), dtype)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((s, 2, 16)), dtype)
    keep = _keep(case, s, block_q, tile_k, rng)
    calls = []

    def key_mask(lo, hi):
        calls.append((lo, hi))
        # the first block causal-only, as the model's is
        return None if lo == 0 else jnp.asarray(keep[lo:hi, :hi])

    mask = None if keep is None else key_mask
    got = blocked_causal_attention(q, k, v, scale=0.3, block_q=block_q,
                                   key_mask=mask)
    blocks = [(lo, min(lo + block_q, s)) for lo in range(0, s, block_q)]
    assert calls == (blocks if mask else [])
    want = reference_blocked_attention(q, k, v, scale=0.3, block_q=block_q,
                                       key_mask=mask)
    assert got.shape == want.shape == (s, 2, 16) and got.dtype == dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=tol)
    if dtype == jnp.bfloat16:
        # and against the oracle in float32 throughout: a bfloat16's
        # rounding of the weights and of the result, no more
        exact = reference_blocked_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), scale=0.3,
            block_q=block_q, key_mask=mask)
        np.testing.assert_allclose(got, exact, atol=3e-2)


@pytest.mark.parametrize("window", [None, 40, 200, 1000],
                         ids=["causal", "under_a_tile", "across_tiles",
                              "over_s"])
@pytest.mark.parametrize("shared", [1, 2, 8])
def test_attention_kernel_walks_a_window_over_shared_heads(monkeypatch,
                                                           shared, window):
    """``nns_masked_attention`` with ``H / H_kv`` query heads a
    key/value head and a ``window``, against the block in plain XLA
    with the key/value heads repeated: no window, one smaller than a
    key tile (both edges in one tile), one that is no multiple of a
    tile (whole tiles behind it are no grid step, the first visited is
    masked, those between are not), one longer than the sequence; S a
    multiple of neither tile."""
    monkeypatch.setattr(sparse_attention, "TILE_Q", 32)
    monkeypatch.setattr(sparse_attention, "TILE_K", 128)
    s, heads, block_q = 420, 8, 96
    rng = np.random.default_rng(shared + (window or 0))
    q = jnp.asarray(rng.standard_normal((s, heads, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((s, heads // shared, 24)),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((s, heads // shared, 16)),
                    jnp.float32)
    got = blocked_causal_attention(q, k, v, scale=0.3, block_q=block_q,
                                   window=window)
    want = reference_blocked_attention(q, k, v, scale=0.3, block_q=block_q,
                                       window=window)
    assert got.shape == want.shape == (s, heads, 16)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    if window is not None and window < s:
        # and the window is live: the causal block differs
        causal = reference_blocked_attention(q, k, v, scale=0.3,
                                             block_q=block_q)
        assert float(jnp.abs(causal - want).max()) > 1e-2


def test_attention_window_narrows_a_selection_and_odd_groups_raise():
    """A ``key_mask`` under a ``window`` keeps the pairs both keep (one
    int8 tile set, the walk still starts at the window's first tile);
    key/value heads that do not divide the query heads, and a window
    that keeps nothing, raise."""
    s, block_q, window = 300, 64, 150
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((s, 6, 24)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((s, 2, 24)), jnp.float32)
            for _ in range(2))
    keep = (rng.random((s, s)) < 0.5) | np.eye(s, dtype=bool)

    def key_mask(lo, hi):
        return None if lo == 0 else jnp.asarray(keep[lo:hi, :hi])

    got = blocked_causal_attention(q, k, v, scale=0.3, block_q=block_q,
                                   key_mask=key_mask, window=window)
    want = reference_blocked_attention(q, k, v, scale=0.3, block_q=block_q,
                                       key_mask=key_mask, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="query heads"):
        blocked_causal_attention(q, k[:, :1].repeat(4, 1), v, scale=1.0,
                                 block_q=block_q)
    with pytest.raises(ValueError, match="window=0"):
        blocked_causal_attention(q, k, v, scale=1.0, block_q=block_q,
                                 window=0)


def test_block_without_a_query_tile_raises():
    x = jnp.zeros((24, 1, 8))
    with pytest.raises(ValueError, match="block_q=12"):
        blocked_causal_attention(x, x, x, scale=1.0, block_q=12)


@pytest.mark.parametrize("uri,dims,calls,equations", [
    # 3 layers x 4 blocks of 16 queries (the fixture's BLOCK_Q); an
    # attention's 2 reshapes, 2 slices, 2 pads and 3 transposes
    # (latent.mla_weights), an expert layer's router bias to float32
    ("zoo://glm_dsa?seq=64&held_first=8&held_count=8",
     ("int32", "64"), {"nns_masked_attention": 12}, 3 * 9 + 2),
    # in float32 the bias is used as it is loaded
    ("zoo://glm_dsa?seq=64&held_first=8&held_count=8&dtype=float32",
     ("int32", "64"), {"nns_masked_attention": 12}, 3 * 9),
    ("zoo://mlp", ("float32", "64:4"), {}, None),
    # every expert of the router held: the tile loops all the same (a
    # sixteenth of a router is what this block's chips hold), no grouped
    # kernel
    ("zoo://glm_dsa?seq=64", ("int32", "64"), {"nns_masked_attention": 12},
     3 * 9 + 2),
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
