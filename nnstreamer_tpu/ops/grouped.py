"""Grouped expert product: each token's rows go through the weights of
the expert it was routed to, and through no other.

A sparse expert layer holds ``G`` of a router's experts and gets, for
``T`` tokens, a choice of ``K`` experts each, any number of them held
here (the others live on other chips). How many token-expert pairs an
expert serves is data, not a shape. :func:`group_by_expert` sorts the
``T x K`` pairs by held expert, the pairs of experts held elsewhere
last, and :func:`grouped_swiglu` serves every pair of a held expert
exactly: no capacity, no dropped pair, nothing for an expert nobody
chose. It takes one of two forms, and **the share the layer holds
decides which** (:func:`takes_kernel`: ``2 G >= router``, the router's
width named by every caller; no option):

* **The kernel**, at least half the router held. The pairs' rows are
  gathered once into a buffer sorted by expert, which must be sized for
  the worst routing, every pair served here: at least half the router
  held, that is at most twice the rows an even routing serves. One
  Pallas TPU kernel, ``nns_grouped_swiglu``, walks it a tile of ``tile``
  rows a grid step, every expert from a tile of its own (:func:`_walk`:
  ``sum(ceil(count / tile))`` steps, :func:`tiles_walked`), runs the
  three products with ``silu(gate) * up`` between them, and adds each of
  its expert's rows, weighted, into its token's row of a float32 ``[T,
  d]`` sum that stays in VMEM through the grid and is written once. A
  step whose own rows fit in ``tile // 2`` multiplies half its tile
  (:func:`_short`, :func:`half_tiles`). The experts' matrices stay in
  HBM and the kernel fetches them itself into a ring of sets in VMEM,
  an expert's first step starting the fetch of the one the ring looks
  ahead to (:func:`_ring`, :func:`_depth`).
* **The tile loops**, any smaller share. Device loops whose trip counts
  are the tiles occupied run one expert on one tile a turn, a row
  gather, three products and a scatter-add: one loop for whole tiles and
  last tiles over half full, one for the last tiles at half the rows.
  Their cost follows the pairs served, where the sorted buffer at a
  sixteenth of a router would be sixteen times the rows served.

Both forms give the same sum (on operands whose products are exact, the
same bits: the tests take each as the other's reference); read on the
chip, each is the faster on its own side of the choice (PERF.md §6).
What the kernel's path must hold:

* **Alignment.** Every expert's run starts on a multiple of the dtype's
  sublane tile (16 rows of bfloat16, :func:`_spread`), and a step's
  block starts at that *element* offset (``pl.Element``): Mosaic takes
  a row of HBM that it can prove such a multiple, and no other. The
  layout is ``T x K + G (align - 1)`` rows and a tile of slack; a free
  slot holds pair 0 at weight 0.
* **What a step adds.** A step multiplies whatever lies behind its
  expert's last row (free slots, the next expert's rows on the wrong
  matrices) and adds its expert's own rows alone; a token meets an
  expert once, so no two adds of a step share a row. The pairs of
  experts held elsewhere lie behind the last held expert's run, where
  no step begins.
* **VMEM and SMEM.** A call asks VMEM for the sum, a step's float32
  rows, the ring's sets of an expert's three matrices, a tile of rows
  twice over and 16 MiB for Mosaic (:func:`_vmem_asked`), within a
  v5e core's 128 MiB, and SMEM for the slots' tokens and weights and the
  walk's scalars, within its 1 MiB (:func:`_most_tokens`). Past either,
  the tokens go in runs as even as they go, a call each, each run's
  pairs grouped anew; each run reads every held expert's matrices again.

Compiled by Mosaic on a TPU, through the Pallas interpreter elsewhere
(how the CPU tests run it).
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
    return _by_key(
        jnp.where((local >= 0) & (local < held_count), local, held_count),
        held_count)


def _by_key(key, held_count: int):
    """:func:`group_by_expert` on each pair's held expert, ``held_count``
    for a pair held elsewhere."""
    counts = jnp.sum(key[:, None] == jnp.arange(held_count)[None, :],
                     axis=0, dtype=jnp.int32)
    return jnp.argsort(key, stable=True), counts


def tiles_walked(counts, tile: int):
    """How many ``tile``-row tiles the aligned walk costs a routing:
    ``counts`` [..., G] pairs an expert (any integer array, numpy's or
    jax's) -> the sum over the experts of ``ceil(count / tile)``
    (:func:`_walk`'s live steps)."""
    return (-(-counts // tile)).sum(-1)


def _short(counts, tile: int):
    """Whether an expert's last tile holds at most half its rows, so
    that it is taken at ``tile // 2`` (the kernel's step, the loops'
    second loop): ``counts`` [..., G] -> bool [..., G]."""
    rest = counts % tile
    return (rest > 0) & (rest <= tile // 2)


def half_tiles(counts, tile: int):
    """How many of the walked tiles (:func:`tiles_walked`) the kernel
    multiplies at half their rows: ``counts`` [..., G] pairs an expert
    (numpy's or jax's) -> the experts whose last tile holds at most
    ``tile // 2`` rows."""
    return _short(counts, tile).sum(-1)


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
    ``(expert, live, row, rows)``, int32: a step's expert ``[tiles]``,
    the experts in turn and an expert's ``ceil(counts[g] / tile)`` tiles
    in turn (one nobody chose has none); :func:`tiles_walked` ``[1]``,
    the steps that hold a row; the row of the buffer laid out at
    ``align`` (:func:`_spread`) at which a step's ``tile`` rows begin
    ``[tiles]``, the expert's run from its first row on; and how many
    of them are its expert's ``[tiles]``, ``min(tile, count - offset)``,
    0 on the steps past the live ones (which repeat the last: nothing
    is fetched for them)."""
    live = tiles_walked(counts, tile)
    at = jnp.clip(jnp.arange(tiles), 0, jnp.maximum(live - 1, 0))
    of = -(-counts // tile)
    expert = jnp.repeat(jnp.arange(counts.shape[0]), of,
                        total_repeat_length=tiles)[at]
    held = -(-counts // align) * align
    offset = (at - (jnp.cumsum(of) - of)[expert]) * tile
    row = (jnp.cumsum(held) - held)[expert] + offset
    rows = jnp.where(jnp.arange(tiles) < live,
                     jnp.minimum(tile, counts[expert] - offset), 0)
    return tuple(a.astype(jnp.int32)
                 for a in (expert, live.reshape(1), row, rows))


def _ring(counts, depth: int):
    """The experts in the order the kernel's walk (:func:`_walk`) meets
    them, each fetched once: ``counts`` [G] -> int32 ``[G + depth - 1]``,
    every expert with a pair in turn, then -1 (no expert: nothing to
    fetch), as far as the ring can look ahead of the last."""
    g = counts.shape[0]
    walked = jnp.argsort(counts == 0, stable=True).astype(jnp.int32)
    return jnp.where(jnp.arange(g + depth - 1) < jnp.sum(counts > 0),
                     jnp.pad(walked, (0, depth - 1)), -1)


def _swiglu_kernel(expert_ref, live_ref, row_ref, rows_ref, ring_ref,
                   token_ref, weight_ref, x_ref, w1_hbm, w3_hbm, w2_hbm,
                   o_ref, w1_ref, w3_ref, w2_ref, sem, nth_ref, y_ref):
    """One grid step (:func:`_walk`): a tile's rows through its
    expert's three matrices, and each of the expert's own rows, weighted,
    added into its token's row of the sum that stays in VMEM. The
    matrices come from a ring of ``depth`` sets in VMEM: the ``k``-th
    expert walked (:func:`_ring`) lies in set ``k % depth``, and its
    first step starts the fetch of the expert ``depth - 1`` ahead into
    the set the last expert left, then waits for its own."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    depth, tile = w1_ref.shape[0], x_ref.shape[0]

    def copies(k):
        slot = k % depth
        return [pltpu.make_async_copy(src.at[ring_ref[k]],
                                      dst.at[slot], sem.at[m, slot])
                for m, (src, dst) in enumerate(((w1_hbm, w1_ref),
                                                (w3_hbm, w3_ref),
                                                (w2_hbm, w2_ref)))]

    def fetch(k):
        @pl.when(ring_ref[k] >= 0)
        def _():
            for c in copies(k):
                c.start()

    @pl.when(i == 0)
    def _():
        t, d = o_ref.shape

        def zero(j, carry):
            o_ref[pl.ds(pl.multiple_of(j * 8, 8), 8), :] = jnp.zeros(
                (8, d), o_ref.dtype)
            return carry
        if t >= 8:          # the loop's body is traced even at no trip
            jax.lax.fori_loop(0, t // 8, zero, 0)
        if t % 8:
            o_ref[t - t % 8:, :] = jnp.zeros((t % 8, d), o_ref.dtype)
        nth_ref[0] = -1
        for k in range(depth - 1):
            fetch(k)

    @pl.when(i < live_ref[0])
    def _():
        new = (i == 0) | (expert_ref[i] != expert_ref[jnp.maximum(i - 1, 0)])

        @pl.when(new)
        def _():
            k = nth_ref[0] + 1
            nth_ref[0] = k
            fetch(k + depth - 1)
            for c in copies(k):
                c.wait()
        slot = nth_ref[0] % depth
        n = rows_ref[i]

        def products(rows: int):
            x = x_ref[pl.ds(0, rows), :]
            gate = jnp.dot(x, w1_ref[slot], preferred_element_type=jnp.float32)
            up = jnp.dot(x, w3_ref[slot], preferred_element_type=jnp.float32)
            y_ref[pl.ds(0, rows), :] = jnp.dot(
                (jax.nn.silu(gate) * up).astype(x.dtype), w2_ref[slot],
                preferred_element_type=jnp.float32)

        # an expert's last tile of at most half its rows (:func:`_short`)
        # is multiplied at half: rows past ``n`` are never added
        half = tile // 2
        if half:
            pl.when(n <= half)(lambda: products(half))
            pl.when(n > half)(lambda: products(tile))
        else:
            products(tile)
        at = row_ref[i]

        # rows past ``rows`` (the free slots, the next expert's rows on
        # these matrices) are never added; a token meets an expert once,
        # so no two adds of a step share a row. Eight rows a turn from a
        # multiple of 8: 18 bundles a row where one a turn takes 24 (the
        # rows' addresses bind the scalar unit; the bundle dump, PR 41)
        def add(r):
            t = token_ref[at + r]
            o_ref[pl.ds(t, 1), :] += weight_ref[at + r] * y_ref[pl.ds(r, 1), :]

        def eight(j, carry):
            for r in range(8):
                add(pl.multiple_of(j * 8, 8) + r)
            return carry

        def one(r, carry):
            add(r)
            return carry
        jax.lax.fori_loop(0, n // 8, eight, 0)
        jax.lax.fori_loop(n // 8 * 8, n, one, 0)


def _aligned_swiglu(xs, token, weight, expert, live, row, rows, ring, w1, w3,
                    w2, *, tokens: int, tile: int, align: int):
    """``xs`` [slots, d], each expert's run from a multiple of ``align``
    rows on (:func:`_spread`); ``token``, ``weight`` [slots] the token
    and the weight of the pair in each slot; ``expert``, ``live``,
    ``row``, ``rows`` from :func:`_walk`, ``ring`` from :func:`_ring`
    at the depth :func:`_depth` gives -> float32 ``[tokens, d]``: for
    each token the sum over the slots of step ``i``'s first ``rows[i]``
    rows that hold it of ``weight * (silu(x w1[g]) * (x w3[g])) w2[g]``
    on the step's expert's matrices, bfloat16 operands as they come,
    float32 accumulation, ``silu(gate) * up`` in float32 rounded once to
    ``xs``' dtype, the sum in float32 in the order of the steps. One
    ``nns_grouped_swiglu`` call. The sum is its output
    block, one for the whole grid, held in VMEM and written once; beside
    it the ring of the experts' matrices, which the kernel fetches from
    HBM itself, so it asks for the VMEM that takes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, f = w1.shape[1:]
    depth = ring.shape[0] - w1.shape[0] + 1

    def rows_in(i, expert, live, row, *_):
        # an element offset, not a block's: Mosaic takes a row of HBM that
        # it can prove a multiple of the sublane tile, and no other
        return pl.multiple_of(row[i], align), 0

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _swiglu_kernel,
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7, grid=expert.shape,
            in_specs=[pl.BlockSpec((pl.Element(tile), pl.Element(d)),
                                   rows_in), hbm, hbm, hbm],
            out_specs=pl.BlockSpec((tokens, d), lambda i, *_: (0, 0),
                                   pipeline_mode=pl.Buffered(1)),
            scratch_shapes=[pltpu.VMEM((depth, d, f), w1.dtype),
                            pltpu.VMEM((depth, d, f), w3.dtype),
                            pltpu.VMEM((depth, f, d), w2.dtype),
                            pltpu.SemaphoreType.DMA((3, depth)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((tile, d), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_asked(tokens, d, f, tile,
                                         xs.dtype.itemsize, depth)),
        interpret=jax.default_backend() != "tpu",
        name="nns_grouped_swiglu",
    )(expert, live, row, rows, ring, token, weight, xs, w1, w3, w2)


# a v5e's per core (jax's ``pltpu.get_tpu_info``); what the kernel asks
# of either must lie within it, or Mosaic refuses the program
_VMEM, _SMEM = 128 << 20, 1 << 20
# the most sets of matrices the ring holds: a fourth helped no more in
# the program (PERF.md §6)
_DEEPEST = 3


def _vmem_asked(tokens: int, d: int, f: int, tile: int, itemsize: int,
                depth: int = 2):
    """The VMEM ``nns_grouped_swiglu`` asks for: the float32 sum of
    ``tokens`` rows and a step's float32 rows, ``depth`` sets of an
    expert's three matrices, a tile of rows twice over, and 16 MiB for
    Mosaic's own."""
    return (tokens + tile) * d * 4 + (depth * 3 * d * f + 2 * tile * d) \
        * itemsize + (16 << 20)


def _depth(tokens: int, d: int, f: int, tile: int, itemsize: int) -> int:
    """How many sets of matrices the kernel's ring holds: as many as VMEM
    holds beside the rest of what it asks (:func:`_vmem_asked`), at least
    two (:func:`_most_tokens` sees to their room), at most
    ``_DEEPEST``."""
    room = _VMEM - _vmem_asked(tokens, d, f, tile, itemsize, 0)
    return max(2, min(_DEEPEST, room // (3 * d * f * itemsize)))


def _most_tokens(d: int, f: int, k: int, experts: int, tile: int,
                 align: int, itemsize: int) -> int:
    """The most tokens one ``nns_grouped_swiglu`` call takes (module
    docstring): the VMEM it asks for with a ring of two sets
    (:func:`_vmem_asked`) and its prefetched scalars (a token and a
    weight a slot, an expert, a row and a row count a step, the ring's
    experts and its count) within the chip's, 64 KiB of SMEM spare."""
    vmem = (_VMEM - _vmem_asked(0, d, f, tile, itemsize)) // (4 * d)
    smem = (_SMEM - (64 << 10) - 8 * (experts * (align - 1) + tile)
            - 16 * experts - 4 * (_DEEPEST + 1)) * tile \
        // (k * (8 * tile + 12))
    return min(vmem, smem)


def takes_kernel(held: int, router: int) -> bool:
    """Whether a chip that holds ``held`` of a router's ``router``
    experts runs the kernel: where it holds at least
    half of them, the sorted buffer's worst case (every pair served
    here) is at most twice the rows served on an even routing."""
    return 2 * held >= router


def grouped_swiglu(x, order, counts, pair_weight, w1, w3, w2, *, tile: int,
                   router: int):
    """``x`` [T, d]; ``order``, ``counts`` from :func:`group_by_expert`;
    ``pair_weight`` [T, K] float32; ``w1``, ``w3`` [G, d, f], ``w2``
    [G, f, d] -> float32 [T, d]: for each token the sum over its pairs
    with a held expert of ``weight * (silu(x w1) * (x w3)) w2``.
    ``tile``: the rows a loop's turn or the kernel's grid step takes.
    ``router``: the width of the router the ``G`` experts are a share
    of, which decides the form (:func:`takes_kernel`)."""
    form = _by_kernel if takes_kernel(counts.shape[0], router) else _by_loops
    return form(x, order, counts, pair_weight, w1, w3, w2, tile=tile)


def _by_kernel(x, order, counts, pair_weight, w1, w3, w2, *, tile: int):
    """:func:`grouped_swiglu` through ``nns_grouped_swiglu``, a call for
    each run of tokens that fits the chip (module docstring)."""
    t, d = x.shape
    k = order.shape[0] // t
    pairs, experts = t * k, counts.shape[0]
    # every expert's run from a multiple of the dtype's sublane tile
    # on (of a smaller ``tile``'s, which only the interpreter takes)
    align = math.gcd(tile, 32 // x.dtype.itemsize)
    most = _most_tokens(d, w1.shape[2], k, experts, tile, align,
                        x.dtype.itemsize)
    if 0 < most < t:
        # more tokens than one call holds: runs of consecutive tokens
        # as even as they go, a call each, each run's pairs grouped
        # anew from each pair's held expert in pair order (the sorted
        # slots' experts, by a sort, no gather)
        run = -(-t // -(-t // most))
        with jax.named_scope("rows"):
            at = jnp.arange(pairs, dtype=jnp.int32)
            _, key = jax.lax.sort((order.astype(jnp.int32), jnp.sum(
                at[:, None] >= jnp.cumsum(counts), axis=1,
                dtype=jnp.int32)), num_keys=1)
            runs = [(a, _by_key(key[a * k:(a + run) * k], experts))
                    for a in range(0, t, run)]
        return jnp.concatenate([
            _by_kernel(x[a:a + run], *grouping, pair_weight[a:a + run],
                       w1, w3, w2, tile=tile)
            for a, grouping in runs])
    tiles = -(-pairs // tile) + experts - 1     # the most a routing takes
    with jax.named_scope("rows"):
        # a tile of slack behind the last expert's run; pairs of
        # experts held elsewhere are sorted last, behind the last held
        # expert's run, where no step begins. The pair and the weight
        # in each slot by sorts, no gather of scalars: the slot of
        # each pair in pair order, then the slots with their pairs
        # and weights (a free slot holds pair 0 at weight 0: a real
        # row, multiplied at most and added by nobody)
        slots = pairs + experts * (align - 1) + tile
        to, gaps = _spread(counts, pairs, align, slots)
        _, to = jax.lax.sort((order.astype(jnp.int32), to), num_keys=1)
        _, pair, weight = jax.lax.sort((
            jnp.concatenate([to, gaps]),
            jnp.concatenate([jnp.arange(pairs, dtype=jnp.int32),
                             jnp.zeros_like(gaps)]),
            jnp.concatenate([pair_weight.reshape(-1).astype(jnp.float32),
                             jnp.zeros(gaps.shape, jnp.float32)])),
            num_keys=1)
        token = pair // k
        xs = x[token]
        walk = _walk(counts, tiles, tile, align)
        ring = _ring(counts, _depth(t, d, w1.shape[2], tile,
                                    x.dtype.itemsize))
    return _aligned_swiglu(xs, token, weight, *walk, ring, w1, w3, w2,
                           tokens=t, tile=tile, align=align)


def _by_loops(x, order, counts, pair_weight, w1, w3, w2, *, tile: int):
    """:func:`grouped_swiglu` through the tile loops (module
    docstring)."""
    t, d = x.shape
    k = order.shape[0] // t
    weight = pair_weight.reshape(-1)
    row0 = jnp.cumsum(counts) - counts           # an expert's first row
    half = tile // 2
    rest = counts % tile
    short = _short(counts, tile)

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
