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
served when a chip holds a sixteenth of the router. **Which form a
layer takes follows from the share it holds** (:func:`takes_kernel`;
what the layer observes of itself, no option): **at least half the
router** (``2 G >= router``: the buffer is then at most twice the rows
an even routing serves; the whole router, where ``T x K`` is exact,
among them) takes the sorted buffer and the kernel below, any smaller
share the loops. Read on the chip at a half, 128 of a router of 256 at
8192 tokens, 2304 x 1024 experts and a skewed routing (26,936 of the
65,536 pairs here, one expert 3,643): the kernel's path **9.41 ms a
layer, the loops 12.07** (PERF.md, PR 38); at a sixteenth the loops
stay (GLM-5's and LongCat's 16 held experts: nothing of theirs
changed). The pairs of experts held elsewhere are sorted last
(:func:`group_by_expert`), so their rows lie behind the last held
expert's run, where no grid step begins, and on the way out they count
as nothing. On that path the rows are
gathered once in expert order, one Pallas TPU kernel,
``nns_grouped_swiglu`` (:func:`_aligned_swiglu`), runs the three
products and ``silu(gate) * up`` between them, and the results go back
to pair order by one more gather, where a token's ``K`` rows lie side
by side and are weighted and summed: no scatter-add, no loop turn.
**Every expert starts on a tile of its own** (PR 37): a grid step is a
tile and a tile has one expert (:func:`_walk`, prefetched scalars), so
a routing costs ``sum(ceil(count / tile))`` steps
(:func:`tiles_walked`), each stored whole: no mask, no read of the
output block. A step multiplies whatever lies behind its expert's last
row (the next experts' rows, on the wrong matrices) and stores it where
nobody reads. An expert nobody chose has no step, and consecutive tiles
of one expert keep its three matrices in VMEM, so each is read from HBM
once. The layouts (:func:`_spread`; sorts and comparisons against the
experts, no gather of scalars): **on the way in every expert's run
starts on a multiple of the dtype's sublane tile** (16 rows of
bfloat16: ``T x K + 15 G`` rows and a tile of slack, 34,944 at the
cell's shapes) and a step's block is ``tile`` rows from an *element*
offset (``pl.Element``), which Mosaic takes from HBM when it can prove
the offset a multiple of that tile and at no other row; **on the way
out every expert's run starts on a tile**, ``ceil(T K / tile) + G - 1``
tiles of which the live ones are written, and the pairs' gather reads
them there. ``gate``, ``up`` and their product never exist in HBM.
Compiled by Mosaic on a TPU, through the Pallas interpreter elsewhere
(how the CPU tests run it). Read on the chip at 128 experts of 2048 x
1024 and 32,768 pairs a layer (PERF.md, PR 34, PR 35 and PR 37), ms a
layer: the loops' whole 16.8 (their scatter-add into the ``[4096,
2048]`` float32 sum alone 9.4); three ``lax.ragged_dot`` s and the
fusion between them (the TPU compiler's own grouped-product kernel, at
24 % of the MXU's peak) 9.07 alone and 9.4 in the program; JAX's
bundled ``megablox.gmm`` three times 6.25 alone at its best tiling, 52
at its default; PR 35's kernel over tiles of the sorted buffer, a tile
shared by several experts a masked step for each (252-254 steps of 256
rows for 128 tiles), 4.56 alone and 4.55 in the program, at 128-row
tiles the same, 6.5 at 512; this one **4.26 alone and 4.21 in the
program** (206 steps of 256 rows; 4.32 and 4.29 at 128 rows, 328 steps:
deleted). A step that changes expert is bound by the memory, not the
MXU: 12.6 MB of matrices, a tile in and a float32 tile out are 15.6 MB,
20.5 us at 760 GB/s, against 18.3 us of products; so fewer steps gave
0.30 ms a layer where 0.78 were sized. The ways in that were tried and
deleted, ms a sequence of four layers in the program against PR 35's
48.05: this kernel on rows gathered in the way out's own layout (65,280
rows a layer) with the slots' pairs gathered as 32-bit scalars 51.13
(the three scalar gathers 3.7), the same with sorts for them 47.71 (the
rows' gather 0.41 a layer for 0.21), a kernel that copies its tile from
the sorted buffer itself at any row: refused by Mosaic, and from
multiples of 16 rows by its own double-buffered ``make_async_copy``:
the ``pl.Element`` block's time with forty lines more; kept, **46.98**.
The three ``ragged_dot`` s' result is this kernel's to the bit.
"""
from __future__ import annotations

import math

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


def tiles_walked(counts, tile: int):
    """How many ``tile``-row tiles the aligned walk costs a routing:
    ``counts`` [..., G] pairs an expert (any integer array, numpy's or
    jax's) -> the sum over the experts of ``ceil(count / tile)``. The
    kernel multiplies that many tiles whole (:func:`_walk`'s live
    steps); the rows served over ``tile`` times it is the share of its
    multiplications that somebody reads."""
    return (-(-counts // tile)).sum(-1)


def _spread(counts, rows: int, align: int, slots: int):
    """``rows`` rows sorted by expert, expert ``g``'s ``counts[g]`` in
    turn, laid out over ``slots`` slots so that every expert's run
    starts on a multiple of ``align``, the experts still in turn ->
    ``(to, gaps)``, int32: the slot of sorted row ``s`` ``[rows]``
    (``s`` plus the slots left free behind the experts that end at or
    before it), and the free slots in turn ``[slots - rows]`` (the
    ``i``-th lies ``i`` behind the rows of every expert whose own free
    slots begin at or before it). Comparisons against the experts and
    sums, no gather: a gather of 32-bit scalars costs the chip 7 ns an
    element (PERF.md, PR 37), each of these 0.015 ms a layer."""
    def behind(at, edges, sizes):
        return at + jnp.sum(jnp.where(at[:, None] >= edges, sizes, 0),
                            axis=1, dtype=jnp.int32)

    free = -counts % align
    return (behind(jnp.arange(rows, dtype=jnp.int32), jnp.cumsum(counts),
                   free),
            behind(jnp.arange(slots - rows, dtype=jnp.int32),
                   jnp.cumsum(free) - free, counts))


def _walk(counts, tiles: int, tile: int, align: int):
    """The kernel's grid, a step a tile and a tile one expert's:
    ``(expert, live, row)``, int32: a step's expert ``[tiles]``, the
    experts in turn and an expert's ``ceil(counts[g] / tile)`` tiles in
    turn (one nobody chose has none); :func:`tiles_walked` ``[1]``, the
    steps that hold a row; and the row of the buffer laid out at
    ``align`` (:func:`_spread`) at which a step's ``tile`` rows begin
    ``[tiles]``, the expert's run from its first row on. The steps past
    the live ones repeat the last (nothing is fetched for them)."""
    live = tiles_walked(counts, tile)
    at = jnp.clip(jnp.arange(tiles), 0, jnp.maximum(live - 1, 0))
    of = -(-counts // tile)
    expert = jnp.repeat(jnp.arange(counts.shape[0]), of,
                        total_repeat_length=tiles)[at]
    held = -(-counts // align) * align
    row = (jnp.cumsum(held) - held)[expert] \
        + (at - (jnp.cumsum(of) - of)[expert]) * tile
    return tuple(a.astype(jnp.int32) for a in (expert, live.reshape(1), row))


def _swiglu_kernel(expert_ref, live_ref, row_ref, x_ref, w1_ref, w3_ref,
                   w2_ref, o_ref):
    """One grid step (:func:`_walk`): a tile's rows through its
    expert's three matrices, stored whole."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype),
                             w2_ref[...], preferred_element_type=jnp.float32)


def _aligned_swiglu(xs, expert, live, row, w1, w3, w2, *, tile: int,
                    align: int):
    """``xs`` [rows, d], each expert's run from a multiple of ``align``
    rows on (:func:`_spread`); ``expert``, ``live``, ``row`` from
    :func:`_walk` -> float32 ``[steps * tile, d]``: step ``i``'s tile
    holds the ``tile`` rows of ``xs`` from ``row[i]`` on, each as
    ``(silu(x w1[g]) * (x w3[g])) w2[g]`` on the step's expert's
    matrices, bfloat16 operands as they come, float32 accumulation,
    ``silu(gate) * up`` in float32 rounded once to ``xs``' dtype; the
    tiles from ``live[0]`` on are left as they are. One
    ``nns_grouped_swiglu`` call (module docstring). It holds an expert's
    three matrices twice over (one set read while the last is
    multiplied), so it asks for the VMEM that takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, f = w1.shape[1:]

    def rows_in(i, expert, live, row):
        # an element offset, not a block's: Mosaic takes a row of HBM that
        # it can prove a multiple of the sublane tile, and no other
        return pl.multiple_of(row[i], align), 0

    def tile_out(i, expert, live, row):
        return jnp.clip(i, 0, jnp.maximum(live[0] - 1, 0)), 0

    def matrices(i, expert, live, row):
        return expert[i], 0, 0

    blocks = (3 * d * f + tile * d) * xs.dtype.itemsize + tile * d * 4
    return pl.pallas_call(
        _swiglu_kernel,
        out_shape=jax.ShapeDtypeStruct((expert.shape[0] * tile, d),
                                       jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=expert.shape,
            in_specs=[pl.BlockSpec((pl.Element(tile), pl.Element(d)),
                                   rows_in),
                      pl.BlockSpec((None, d, f), matrices),
                      pl.BlockSpec((None, d, f), matrices),
                      pl.BlockSpec((None, f, d), matrices)],
            out_specs=pl.BlockSpec((tile, d), tile_out)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * blocks + (16 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="nns_grouped_swiglu",
    )(expert, live, row, xs, w1, w3, w2)


def takes_kernel(held: int, router: int) -> bool:
    """Whether a chip that holds ``held`` of a router's ``router``
    experts runs the kernel (module docstring): where it holds at least
    half of them, the sorted buffer's worst case (every pair served
    here) is at most twice the rows served on an even routing."""
    return 2 * held >= router


def grouped_swiglu(x, order, counts, pair_weight, w1, w3, w2, *, tile: int,
                   router: int = 0):
    """``x`` [T, d]; ``order``, ``counts`` from :func:`group_by_expert`;
    ``pair_weight`` [T, K] float32; ``w1``, ``w3`` [G, d, f], ``w2``
    [G, f, d] -> float32 [T, d]: for each token the sum over its pairs
    with a held expert of ``weight * (silu(x w1) * (x w3)) w2``.
    ``tile``: the rows a loop's turn or the kernel's grid step takes.
    ``router``: the width of the router the ``G`` experts are a share of
    (what the layer knows of itself, not a choice of form: the share
    decides, :func:`takes_kernel`; a caller that gives none keeps the
    loops)."""
    t, d = x.shape
    k = order.shape[0] // t
    if router and takes_kernel(counts.shape[0], router):
        pairs, experts = t * k, counts.shape[0]
        tiles = -(-pairs // tile) + experts - 1     # the most a routing takes
        # the way in: every expert's run from a multiple of the dtype's
        # sublane tile on (of a smaller ``tile``'s, which only the
        # interpreter takes), a tile of slack behind the last; the pair
        # in each slot by one sort of the slots (a free slot holds pair
        # 0: a real row, multiplied at most and read by nobody). Pairs
        # of experts held elsewhere are sorted last: their rows lie
        # behind the last held expert's, where no step begins
        align = math.gcd(tile, 32 // x.dtype.itemsize)
        slots = pairs + experts * (align - 1) + tile
        to, gaps = _spread(counts, pairs, align, slots)
        _, pair = jax.lax.sort((jnp.concatenate([to, gaps]), jnp.concatenate(
            [order.astype(jnp.int32), jnp.zeros_like(gaps)])), num_keys=1)
        y = _aligned_swiglu(x[pair // k], *_walk(counts, tiles, tile, align),
                            w1, w3, w2, tile=tile, align=align)
        # the way out: every expert's run from a tile of its own on; back
        # in pair order, a token's K rows side by side, weighted, summed
        to, _ = _spread(counts, pairs, tile, tiles * tile)
        if experts == router:
            _, slot = jax.lax.sort((order.astype(jnp.int32), to), num_keys=1)
            rows = y[slot]
        else:
            # a pair served elsewhere has no row here: it reads the first
            # and counts as nothing (not as 0 times it: a tile no step
            # wrote holds whatever the memory held)
            here = jnp.arange(pairs) < jnp.sum(counts)
            _, slot, here = jax.lax.sort(
                (order.astype(jnp.int32), jnp.where(here, to, 0), here),
                num_keys=1)
            rows = jnp.where(here[:, None], y[slot], 0.0)
        return jnp.sum(rows.reshape(t, k, d) * pair_weight[:, :, None],
                       axis=1)
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
