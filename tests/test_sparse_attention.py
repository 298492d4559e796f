"""``ops/sparse_attention.py``: the blocked causal attention and its
Pallas kernel ``nns_masked_attention`` (a key mask, a window, key/value
heads shared by several query heads) against the plain block, at tiny
sizes on the CPU through the Pallas interpreter."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.ops import sparse_attention
from nnstreamer_tpu.ops.sparse_attention import (
    blocked_causal_attention, reference_blocked_attention)


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


@pytest.mark.parametrize("s,window", [(1536, 513), (3072, 2048)],
                         ids=["window_513", "window_2048"])
def test_window_walk_at_the_models_tiles_is_dense_masked_attention(s,
                                                                   window):
    """The window walk at the tiles the models pass (512 queries, 512
    keys) against softmax attention written out over the whole ``[S,
    S]`` mask: a window of one tile plus one key (513: each query tile
    walks its own tile and the one before, both masked) and one of four
    tiles (2048: the walk's middle tiles unmasked), latent heads of
    ``Dk`` 256 and ``Dv`` 128."""
    assert (sparse_attention.TILE_Q, sparse_attention.TILE_K) == (512, 512)
    rng = np.random.default_rng(window)
    q, k = (jnp.asarray(rng.standard_normal((s, 2, 256)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((s, 2, 128)), jnp.float32)
    got = blocked_causal_attention(q, k, v, scale=0.1, block_q=512,
                                   window=window)
    ahead = np.arange(s)[:, None] - np.arange(s)[None, :]
    keep = (ahead >= 0) & (ahead < window)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) * 0.1
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    want = jnp.einsum("hqk,khd->qhd", probs, v,
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the window is live: the last query loses keys to it
    causal = jax.nn.softmax(jnp.where((ahead >= 0)[None], scores, -jnp.inf),
                            -1)
    assert float(jnp.abs(causal[:, -1] - probs[:, -1]).max()) > 1e-3


@pytest.mark.parametrize("s,block_q,window,tiles", [
    # query tile i walks key tiles i - 1 and i, tile 0 its own alone
    (1536, 512, 513, 1 + 2 + 2),
    (8192, 512, 513, 1 + 15 * 2),
    # tiles 1, 2, 3, 4, then 5 each: a window of four tiles reaches back
    # four and walks the diagonal
    (3072, 512, 2048, 1 + 2 + 3 + 4 + 5 + 5),
    # causal: every tile up to the diagonal
    (1536, 512, None, 1 + 2 + 3),
    # a window of under a tile: the tile before while the window reaches
    # back into it, which it does from every tile's first query
    (1536, 512, 40, 1 + 2 + 2),
])
def test_pairs_walked_counts_the_visited_tiles(s, block_q, window, tiles):
    """``pairs_walked``: whole 512 x 512 tiles, as the kernel's grid
    visits them, counted by hand."""
    assert sparse_attention.pairs_walked(s, block_q=block_q,
                                         window=window) == tiles * 512 * 512
