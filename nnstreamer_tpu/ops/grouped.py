"""Grouped expert product: each token's rows go through the weights of
the expert it was routed to, and through no other.

A sparse expert layer holds ``G`` experts and gets, for ``T`` tokens, a
choice of ``K`` experts each, of which any number may be held here
(the others live on other chips). How many token-expert pairs an expert
serves is data, not a shape, so the dense forms either drop pairs over
a capacity or multiply every token with every expert. This one does
neither: the pairs are sorted by expert (:func:`group_by_expert`), each
expert's run is cut into tiles of ``tile`` rows, and device loops whose
trip counts are the tiles actually occupied run one SwiGLU expert on
one tile a turn (:func:`grouped_swiglu`): one loop for the whole tiles
and the last ones more than half full, one that takes an expert's last
tile at half the rows where half hold it. Exact, no capacity, no
dropped pair; the cost follows the pairs served, padded to half a tile
an expert. ``jax.lax.ragged_dot`` states the same product on a buffer
of the rows sorted by expert, which must be sized for the worst routing,
``T x K`` rows, where the loops need one tile: sixteen times the rows
served when a chip holds a sixteenth of the router. **When every expert
of the router is held** (``whole``), ``T x K`` is exact: every pair is
served here and none belongs to another chip. Then the rows are
gathered once in expert order, three ``ragged_dot`` s run over the
whole buffer (the TPU compiler has a grouped-product kernel of its own
for them), and the results go back to pair order by one more gather,
where a token's ``K`` rows lie side by side and are weighted and
summed: no scatter-add, no loop turn. Read on the chip at 128 experts
of 2048 x 1024 and 32,768 pairs a layer (PERF.md, PR 34): the loops'
scatter-add into the ``[4096, 2048]`` float32 sum alone took 9.4 ms a
layer, their whole 16.8; the sorted form 13.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def group_by_expert(choice, held_first: int, held_count: int):
    """``choice`` [T, K] int32 expert ids over the whole router ->
    ``(order, counts)``: the ``T * K`` pairs (pair ``p`` is token
    ``p // K``) sorted by expert, pairs of experts outside
    ``[held_first, held_first + held_count)`` last, a stable sort; and
    how many pairs each held expert serves, int32 [held_count]."""
    local = choice.reshape(-1) - held_first
    key = jnp.where((local >= 0) & (local < held_count), local, held_count)
    counts = jnp.sum(key[:, None] == jnp.arange(held_count)[None, :],
                     axis=0, dtype=jnp.int32)
    return jnp.argsort(key, stable=True), counts


def grouped_swiglu(x, order, counts, pair_weight, w1, w3, w2, *, tile: int,
                   whole: bool = False):
    """``x`` [T, d]; ``order``, ``counts`` from :func:`group_by_expert`;
    ``pair_weight`` [T, K] float32; ``w1``, ``w3`` [G, d, f], ``w2``
    [G, f, d] -> float32 [T, d]: for each token the sum over its pairs
    with a held expert of ``weight * (silu(x w1) * (x w3)) w2``.
    ``whole``: the ``G`` experts are the whole router, so every one of
    the ``T x K`` pairs is served here (module docstring)."""
    t, d = x.shape
    k = order.shape[0] // t
    if whole:
        # the rows sorted by expert, gathered once: T x K of them, exact
        xs = x[order // k]
        up = jax.lax.ragged_dot(xs, w3, counts,
                                preferred_element_type=jnp.float32)
        gate = jax.lax.ragged_dot(xs, w1, counts,
                                  preferred_element_type=jnp.float32)
        y = jax.lax.ragged_dot((jax.nn.silu(gate) * up).astype(x.dtype), w2,
                               counts, preferred_element_type=jnp.float32)
        # back in pair order: a token's K rows side by side, weighted, summed
        y = y[jnp.argsort(order)].reshape(t, k, d)
        return jnp.sum(y * pair_weight[:, :, None], axis=1)
    weight = pair_weight.reshape(-1)
    row0 = jnp.cumsum(counts) - counts           # an expert's first row
    half = tile // 2
    rest = counts % tile
    short = (rest > 0) & (rest <= half)          # last tile at most half full

    def turns(acc, tiles, rows: int, first):
        """``acc`` plus ``tiles[g]`` tiles of ``rows`` rows of each expert
        ``g`` from its row ``first[g]`` on, a tile a turn of a device
        loop: a row gather, three products on ``w3[g]``, ``w1[g]``,
        ``w2[g]`` with ``g`` the loop's own scalar, a scatter-add."""
        tile_end = jnp.cumsum(tiles)

        def one_tile(i, acc):
            g = jnp.sum(tile_end <= i, dtype=jnp.int32)  # this tile's expert
            row = first[g] + (i - (tile_end[g] - tiles[g])) * rows \
                + jnp.arange(rows)
            live = row < counts[g]
            pair = order[jnp.where(live, row0[g] + row, 0)]
            tok = pair // k
            xt = x[tok]
            up = jnp.dot(xt, w3[g], preferred_element_type=jnp.float32)
            gate = jnp.dot(xt, w1[g], preferred_element_type=jnp.float32)
            y = jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype), w2[g],
                        preferred_element_type=jnp.float32)
            y = y * jnp.where(live, weight[pair], 0.0)[:, None]
            # a dead row's index lies past the end and is dropped
            return acc.at[jnp.where(live, tok, t)].add(y, mode="drop")

        return jax.lax.fori_loop(0, tile_end[-1], one_tile, acc)

    acc = turns(jnp.zeros((t, d), jnp.float32),
                (counts + tile - 1) // tile - short, tile,
                jnp.zeros_like(counts))
    if half:
        acc = turns(acc, short.astype(jnp.int32), half, counts - rest)
    return acc
