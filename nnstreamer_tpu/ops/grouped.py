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
expert's run, where no grid step begins, and nothing of them is added.
On that path the rows are gathered once in expert order, and one Pallas
TPU kernel, ``nns_grouped_swiglu`` (:func:`_aligned_swiglu`), runs the
three products and ``silu(gate) * up`` between them and **adds each
served pair's row, weighted, into its token's row of a float32 ``[T,
d]`` sum that stays in VMEM through the grid** and is written to HBM
once (PR 41): no row of the router's ``T x K`` goes back to pair order,
no pair served elsewhere is read, no scatter-add, no loop turn around
the kernel. **Every expert starts on a tile of its own** (PR 37): a
grid step is a tile and a tile has one expert (:func:`_walk`,
prefetched scalars), so a routing costs ``sum(ceil(count / tile))``
steps (:func:`tiles_walked`). A step multiplies whatever lies behind its
expert's last row (the next experts' rows, on the wrong matrices, or
free slots) and adds only its expert's own rows, ``min(tile, count -
offset)`` of them; a token meets an expert once, so no two adds of a
step share a row, and the steps run in turn. An expert nobody chose has
no step, and consecutive tiles of one expert keep its three matrices in
VMEM, so each is read from HBM once. The layout (:func:`_spread`; sorts
and comparisons against the experts, no gather of scalars): **every
expert's run starts on a multiple of the dtype's sublane tile** (16
rows of bfloat16: ``T x K + 15 G`` rows and a tile of slack, 34,944 at
the Trinity cell's shapes) and a step's block is ``tile`` rows from an
*element* offset (``pl.Element``), which Mosaic takes from HBM when it
can prove the offset a multiple of that tile and at no other row; the
token and the weight of the pair in each slot ride the slots' sort and
are prefetched whole into SMEM. ``gate``, ``up``, their product and the
rows out never exist in HBM.
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
**The way out (PR 41).** Before it, the kernel stored each tile whole
in float32 (every expert's run from a tile of its own) and one more
gather brought all ``T x K`` pairs back in pair order, a pair served
elsewhere reading a row it then set to zero, for a weighted sum over
``K``: in the program, ms a sequence of four layers, 20.45 around the
Kimi-Linear cell's 17.82 of kernel (the gather 14.38, the sum 5.21,
sorts 0.83) and 8.14 around the Trinity cell's 16.85. Now the kernel
adds its rows into the float32 sum in VMEM, the sum its one output
block (``pl.Buffered(1)``): the rows' way in and the sorts 2.94 and
1.51, the kernel 16.62 and 15.78 (no float32 tile written a step any
more, ≈ 18 and 15.5 bundles an added row, eight rows a turn; one row a
turn 24), the period 102.28 -> 80.74 and 47.05 -> 37.99 (PERF.md, PR
41). The forms tried, the op alone on the chip, ms a layer of the
Kimi-Linear / Trinity cell (the tile loops 14.79 / 11.39): this one
5.74 / 4.32; the sum as a VMEM scratch copied out at the last step 5.76
/ 4.32; one row a turn 5.87 / 4.52; the aligned 8-row group that holds
the token's row, picked by an iota compare, 5.96 / 4.53; the slots'
tokens and weights in per-step SMEM blocks, a third sort, 5.86 / 4.43;
the last two and the scratch together 6.11 / 4.65: all exact against
the loops, deleted. The slots' tokens and weights prefetched whole
into SMEM take 268 KB each of its 1 MiB at the Kimi-Linear cell's
67,712 slots. **The weights pipeline and the half tile.** Pallas'
own pipeline fetches a step's blocks only during the step before it, so
every step that changed expert waited for its 12.6 MB of matrices
(20.5 us at 93 % of the memory's peak against 18.3 us of products) and
a short last tile gained nothing while it waited. So the matrices stay
in HBM (``memory_space=ANY``) and the kernel fetches them itself into a
ring of ``depth`` sets in VMEM scratch with a DMA semaphore each
(:func:`_swiglu_kernel`): the order of the experts is the walk's
(:func:`_ring`, an expert with no step never fetched), an expert's
first step starts the copy of the expert ``depth - 1`` ahead into the
set the last one left and waits for its own, and the first step of all
starts the first ``depth - 1``. ``depth`` (:func:`_depth`) is as many
sets as VMEM holds beside the sum, a step's float32 rows, a tile of rows
twice over and 16 MiB, at least 2 and at most 3: at the Trinity cell's
widths (the sum of 4096 rows of 2048 35.7 MB, a set 12.6 MB) six fit
and three are taken; at the Kimi-Linear cell's (8192 rows of 2304, the
sum 75.5 MB, a set 14.2 MB) two sets already ask 125.3 MB of the 128
MiB, a third would cut the call into runs that read every expert's
1.81 GB again, and the ring stays at 2. With the fetch ahead a step is
bound by its products, and **a step whose own rows (``rows[i]``) are at
most ``tile // 2`` multiplies only the first half of its tile**
(:func:`_short`, :func:`half_tiles`: the rows it adds are the same,
and so are their bits). Read on a TPU v5e (PERF.md §6), ms, the
parent's kernel against rings of 2 / 3 / 4 / 6: the op alone at 4096
tokens of 2048 on a skewed routing (195 tiles, 71 of them at half)
4.15 against 3.87 / 3.61 / 3.62 / 4.35, the sum the parent's to the
bit at every depth; the Trinity cell's whole program a sequence 38.45
against 37.53 / **36.29** / 36.27 / 36.15: a fourth set no faster than
the third (the rounds spread 0.03), six 0.4 % faster in the program
and 20 % slower alone, so 3 is the most taken; the Kimi-Linear cell's
widths at a ring of 2, the op alone 6.62 against 6.31, its program
81.76 against 80.22. **What one call holds** (:func:`_most_tokens`): what it
asks of VMEM (the sum, a step's float32 rows, two sets of an expert's
matrices, a tile of rows twice over, 16 MiB spare) within a v5e's 128
MiB, and its prefetched scalars within its 1 MiB of SMEM, 64 KiB spare:
9,159 tokens at the Kimi-Linear cell's widths (VMEM binds: Mosaic
refuses one sum of 16,384, 151 MB), 10,752 at Trinity's, 14,967 at
experts of 512 (SMEM binds: one call of 20,000 tokens asks 1.25 MiB of
it). Past that the
tokens go in runs as even as they go, a call each, each run's pairs
grouped anew from the sorted pairs' experts by one sort; each run reads
every held expert's matrices again (1.81 GB a layer at Kimi-Linear's
widths). The second kernel the issue held in reserve (``y``
written at live-tile size and added by a walk of its own) was not
built: it adds the same rows and writes and reads ``y`` besides.
The three ``ragged_dot`` s' result is this kernel's to the bit (on
operands whose products and weights are exact, the order of a token's
``K`` additions, now the experts', has no say).
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
    ``nns_grouped_swiglu`` call (module docstring). The sum is its output
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
# the program (module docstring)
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
                grouped_swiglu(x[a:a + run], *grouping,
                               pair_weight[a:a + run], w1, w3, w2,
                               tile=tile, router=router)
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
