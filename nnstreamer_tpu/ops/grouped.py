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
gathered once in expert order, one Pallas TPU kernel,
``nns_grouped_swiglu`` (:func:`_sorted_swiglu`), runs the three
products and ``silu(gate) * up`` between them over the whole buffer,
and the results go back to pair order by one more gather, where a
token's ``K`` rows lie side by side and are weighted and summed: no
scatter-add, no loop turn. The kernel walks the buffer in tiles of
``tile`` rows, a grid step an (expert, row tile) pair that share rows
(:func:`_walk`, prefetched scalars): a tile that straddles experts is
visited once for each with the others' rows masked at the store, an
expert nobody chose is never visited, and consecutive tiles of one
expert keep its three matrices in VMEM, so each is read from HBM once.
``gate``, ``up`` and their product never exist in HBM. Compiled by
Mosaic on a TPU, through the Pallas interpreter elsewhere (how the CPU
tests run it). Read on the chip at 128 experts of 2048 x 1024 and
32,768 pairs a layer (PERF.md, PR 34 and PR 35), ms a layer: the
loops' whole 16.8 (their scatter-add into the ``[4096, 2048]`` float32
sum alone 9.4); three ``lax.ragged_dot`` s and the fusion between them
(the TPU compiler's own grouped-product kernel, at 24 % of the MXU's
peak) 9.07 alone and 9.4 in the program; JAX's bundled ``megablox.gmm``
three times 6.25 alone at its best tiling, 52 at its default; this
kernel 4.64 alone and 4.5-4.6 in the program, at 256-row tiles and at
128 alike (fewer masked rows at a lower MXU rate), 6.5 at 512; as two
kernels inside the default 16 MB of VMEM (``h`` through HBM) 5.1 alone
and 2.2 a layer more in the program. The three ``ragged_dot`` s'
result is this kernel's to the bit.
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


def _walk(counts, rows: int, tile: int):
    """The kernel's grid, a step an (expert, row tile) pair that share
    rows of the buffer sorted by expert (``rows`` of them, a multiple of
    ``tile``): int32 ``[rows // tile + G - 1]`` each of the step's
    expert, its row tile, and the tile's first and one-past-last row
    that the expert serves. Experts in turn and an expert's tiles in
    turn, so an expert's steps are consecutive and so are a tile's; an
    expert that serves nobody has no step. The steps past the last one
    (there are fewer than the bound unless every expert starts inside a
    tile) repeat it with no row served: nothing is fetched for them."""
    steps = rows // tile + counts.shape[0] - 1
    end = jnp.cumsum(counts)
    start = end - counts
    first = start // tile                        # an expert's first tile
    tiles = jnp.where(counts > 0, (end + tile - 1) // tile - first, 0)
    before = jnp.cumsum(tiles) - tiles           # steps before an expert's
    step = jnp.arange(steps, dtype=jnp.int32)
    at = jnp.minimum(step, jnp.sum(tiles) - 1)   # the last live step
    expert = jnp.repeat(jnp.arange(counts.shape[0], dtype=jnp.int32), tiles,
                        total_repeat_length=steps)[at]
    row_tile = first[expert] + at - before[expert]
    lo = jnp.clip(start[expert] - row_tile * tile, 0, tile)
    hi = jnp.where(step == at, jnp.clip(end[expert] - row_tile * tile, 0,
                                        tile), lo)
    return tuple(a.astype(jnp.int32) for a in (expert, row_tile, lo, hi))


def _swiglu_kernel(expert_ref, tile_ref, lo_ref, hi_ref, x_ref, w1_ref,
                   w3_ref, w2_ref, o_ref):
    """One grid step (:func:`_walk`): the step's expert over the rows of
    its tile, stored where the expert serves them; the tile's other
    rows keep what their own experts' steps store."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    lo, hi = lo_ref[i], hi_ref[i]

    @pl.when(hi > lo)
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
        y = jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype), w2_ref[...],
                    preferred_element_type=jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        o_ref[...] = jnp.where((rows >= lo) & (rows < hi), y, o_ref[...])


def _sorted_swiglu(xs, counts, w1, w3, w2, *, tile: int):
    """``xs`` [R, d] sorted by expert, expert ``g``'s ``counts[g]`` rows
    after expert ``g - 1``'s and ``sum(counts) == R`` -> float32 [R, d]:
    each row's ``(silu(x w1[g]) * (x w3[g])) w2[g]`` on its own
    expert's matrices, bfloat16 operands as they come, float32
    accumulation, ``silu(gate) * up`` in float32 rounded once to ``xs``'
    dtype. One ``nns_grouped_swiglu`` call (module docstring). It holds
    an expert's three matrices twice over (one set read while the last
    is multiplied), so it asks for the VMEM that takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = xs.shape
    f = w1.shape[2]
    padded = -(-rows // tile) * tile
    if padded != rows:
        xs = jnp.pad(xs, ((0, padded - rows), (0, 0)))
    walk = _walk(counts, padded, tile)

    def row_tile(i, expert, tile_of, lo, hi):
        return tile_of[i], 0

    def matrices(i, expert, tile_of, lo, hi):
        return expert[i], 0, 0

    blocks = (3 * d * f + tile * d) * xs.dtype.itemsize + tile * d * 4
    out = pl.pallas_call(
        _swiglu_kernel,
        out_shape=jax.ShapeDtypeStruct((padded, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(walk), grid=walk[0].shape,
            in_specs=[pl.BlockSpec((tile, d), row_tile),
                      pl.BlockSpec((None, d, f), matrices),
                      pl.BlockSpec((None, d, f), matrices),
                      pl.BlockSpec((None, f, d), matrices)],
            out_specs=pl.BlockSpec((tile, d), row_tile)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * blocks + (16 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="nns_grouped_swiglu",
    )(*walk, xs, w1, w3, w2)
    return out[:rows]


def grouped_swiglu(x, order, counts, pair_weight, w1, w3, w2, *, tile: int,
                   whole: bool = False):
    """``x`` [T, d]; ``order``, ``counts`` from :func:`group_by_expert`;
    ``pair_weight`` [T, K] float32; ``w1``, ``w3`` [G, d, f], ``w2``
    [G, f, d] -> float32 [T, d]: for each token the sum over its pairs
    with a held expert of ``weight * (silu(x w1) * (x w3)) w2``.
    ``tile``: the rows a loop's turn or the kernel's grid step takes.
    ``whole``: the ``G`` experts are the whole router, so every one of
    the ``T x K`` pairs is served here (module docstring)."""
    t, d = x.shape
    k = order.shape[0] // t
    if whole:
        # the rows sorted by expert, gathered once: T x K of them, exact
        y = _sorted_swiglu(x[order // k], counts, w1, w3, w2, tile=tile)
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
