"""Grouped expert product: each token's rows go through the weights of
the expert it was routed to, and through no other.

A sparse expert layer holds ``G`` of a router's experts and gets, for
``T`` tokens, a choice of ``K`` experts each, any number of them held
here (the others live on other chips). How many token-expert pairs an
expert serves is data, not a shape. :func:`group_by_expert` sorts the
``T x K`` pairs by held expert, the pairs of experts held elsewhere
last, and :func:`grouped_swiglu` serves every pair of a held expert
exactly: no capacity, no dropped pair, nothing for an expert nobody
chose. Both of its forms walk the sorted rows a tile of ``tile`` rows a
grid step, every expert from a tile of its own (:func:`_walk`:
``sum(ceil(count / tile))`` steps, :func:`tiles_walked`), a step whose
own rows fit in ``tile // 2`` at half its tile (:func:`_at_half`,
:func:`half_tiles`), and add each of its expert's rows, weighted, into
its token's row of a float32 sum that stays in VMEM and is written once.
**The share the layer holds decides the form**, in one place
(:func:`takes_split`: under half the router, ``2 G < router``, the
router's width named by every caller; no option):

* **The kernel**, at least half the router held (Trinity's whole
  router, Kimi-Linear's half). The pairs' rows are gathered once into a
  buffer sorted by expert, sized for the worst routing, every pair
  served here: at most twice the rows an even routing serves. One Pallas
  TPU kernel, ``nns_grouped_swiglu``, runs a tile's three products with
  ``silu(gate) * up`` between them and adds into a ``[T, d]`` sum. The
  experts' matrices stay in HBM and the kernel fetches them itself into
  a ring of sets in VMEM, an expert's first step starting the fetch of
  the one the ring looks ahead to (:func:`_ring`, :func:`_depth`).
* **The split form**, any smaller share (dots3's 32 of 256, GLM-5's 16
  of 256, LongCat's 16 of 768). A buffer sized for every pair would be
  eight to forty-eight times the rows served; and the kernel has no room
  at these widths: a ``[T, d]`` float32 sum beside two sets of an
  expert's matrices leaves VMEM for 614 tokens at dots3's (``d`` 5120,
  ``f`` 1536; :func:`_most_tokens`), fourteen calls each reading the
  layer's 1.5 GB of matrices again, and for none at GLM-5's and
  LongCat's (``d`` 6144, ``f`` 2048: two sets are 151 MB). So the
  SwiGLU is split at its activation, and neither kernel holds a whole
  expert or the whole sum (:func:`_split_swiglu`). The rows are gathered
  in passes of twice the pairs an even routing serves here
  (:func:`_pass_pairs`); ``nns_grouped_gate_up`` writes a step's
  ``silu(x w1) * (x w3)`` rows, rounded once to the rows' dtype, to HBM,
  and ``nns_grouped_down`` multiplies them by ``w2`` a block of ``dc``
  columns at a time and adds into a ``[T, dc]`` block of the sum
  (:func:`_split_blocks`). A routing that serves more pairs here than a
  pass holds takes the same kernels over the next rows, as many passes
  as it needs, each added into the sum.

Both forms give the same sum (on operands whose products are exact, the
same bits: the tests take each as the other's reference); read on the
chip, each is the faster on its own side of the choice, and the split
form beat the tile loops it replaced in all three cells below half a
router (PERF.md §6). What both must hold:

* **Alignment.** Every expert's run starts on a multiple of the dtype's
  sublane tile (16 rows of bfloat16, :func:`_spread`), and a step's
  block starts at that *element* offset (``pl.Element``): Mosaic takes
  a row of HBM that it can prove such a multiple, and no other. The
  layout is the rows, ``G (align - 1)`` and a tile of slack; a free slot
  holds pair 0 at weight 0. The pair and the weight in each slot come
  by sorts, never by a gather of 32-bit scalars.
* **What a step adds.** A step multiplies whatever lies behind its
  expert's last row (free slots, the next expert's rows on the wrong
  matrices) and adds its expert's own rows alone; a token meets an
  expert once, so no two adds of a step share a row. The pairs of
  experts held elsewhere lie behind the last held expert's run, where
  no step begins.
* **VMEM and SMEM.** What a kernel asks of VMEM (:func:`_vmem_asked`,
  :func:`_gate_up_vmem`, :func:`_down_vmem`) lies within a v5e core's
  128 MiB, 16 MiB of it for Mosaic, and the slots' tokens and weights
  and the walk's scalars within its 1 MiB of SMEM. Past either, the
  kernel takes the tokens in runs as even as they go, a call each, each
  run's pairs grouped anew and every held expert's matrices read again
  (:func:`_most_tokens`); the split form takes narrower blocks of
  columns and smaller passes.

Compiled by Mosaic on a TPU, through the Pallas interpreter elsewhere
(how the CPU tests run it).
"""
from __future__ import annotations

import functools
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


def half_tiles(counts, tile: int):
    """How many of the walked tiles (:func:`tiles_walked`) the kernels
    multiply at half their rows (:func:`_at_half`): ``counts`` [..., G]
    pairs an expert (numpy's or jax's) -> the experts whose last tile
    holds at most ``tile // 2`` rows."""
    rest = counts % tile
    return ((rest > 0) & (rest <= tile // 2)).sum(-1)


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
        _zero(o_ref)
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

        _at_half(n, tile, products)
        _add_rows(o_ref, y_ref, token_ref, weight_ref, row_ref[i], n)


def _zero(o_ref):
    """Zeros into the float32 sum block ``o_ref`` [t, d], eight rows a
    turn of a device loop."""
    from jax.experimental import pallas as pl
    t, d = o_ref.shape

    def zero(j, carry):
        o_ref[pl.ds(pl.multiple_of(j * 8, 8), 8), :] = jnp.zeros(
            (8, d), o_ref.dtype)
        return carry
    if t >= 8:          # the loop's body is traced even at no trip
        jax.lax.fori_loop(0, t // 8, zero, 0)
    if t % 8:
        o_ref[t - t % 8:, :] = jnp.zeros((t % 8, d), o_ref.dtype)


def _at_half(n, tile: int, products):
    """``products(rows)`` at ``tile // 2`` rows where a step's ``n`` rows
    fit there, an expert's last tile of at most half its rows
    (:func:`half_tiles`), else at ``tile``: rows past ``n`` are never
    added."""
    from jax.experimental import pallas as pl
    half = tile // 2
    if half:
        pl.when(n <= half)(lambda: products(half))
        pl.when(n > half)(lambda: products(tile))
    else:
        products(tile)


def _add_rows(o_ref, y_ref, token_ref, weight_ref, at, n):
    """Each of a step's first ``n`` float32 rows ``y_ref``, weighted,
    added into its token's row of the sum block ``o_ref``: row ``r`` is
    slot ``at + r``'s (``token_ref``, ``weight_ref``, in SMEM)."""
    from jax.experimental import pallas as pl

    # rows past ``n`` (the free slots, the next expert's rows on the
    # step's matrices) are never added; a token meets an expert once,
    # so no two adds of a step share a row. Eight rows a turn from a
    # multiple of 8: 18 bundles a row where one a turn takes 24 (the
    # rows' addresses bind the scalar unit, by the kernel's bundle dump)
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


def takes_split(held: int, router: int) -> bool:
    """Whether a chip that holds ``held`` of a router's ``router``
    experts runs the split form: where it holds less than half of them,
    so that the kernel's sorted buffer, sized for every pair, would be
    more than twice the rows an even routing serves."""
    return not takes_kernel(held, router)


def grouped_swiglu(x, order, counts, pair_weight, w1, w3, w2, *, tile: int,
                   router: int):
    """``x`` [T, d]; ``order``, ``counts`` from :func:`group_by_expert`;
    ``pair_weight`` [T, K] float32; ``w1``, ``w3`` [G, d, f], ``w2``
    [G, f, d] -> float32 [T, d]: for each token the sum over its pairs
    with a held expert of ``weight * (silu(x w1) * (x w3)) w2``.
    ``tile``: the rows a kernel's grid step takes.
    ``router``: the width of the router the ``G`` experts are a share
    of, which decides the form (:func:`takes_split`)."""
    if takes_split(counts.shape[0], router):
        return _by_split(x, order, counts, pair_weight, w1, w3, w2,
                         tile=tile, router=router)
    return _by_kernel(x, order, counts, pair_weight, w1, w3, w2, tile=tile)


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


def _gate_up_kernel(expert_ref, live_ref, row_ref, rows_ref, x_ref, w1_ref,
                    w3_ref, h_ref):
    """One grid step (:func:`_walk`) of ``nns_grouped_gate_up``: a
    tile's rows through its expert's ``w1`` and ``w3`` (a block of
    their columns), ``silu(gate) * up`` in float32 rounded once to the
    rows' dtype, stored as the step's own block of ``h``."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)

    @pl.when(i < live_ref[0])
    def _():
        def products(rows: int):
            x = x_ref[pl.ds(0, rows), :]
            gate = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
            up = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
            h_ref[pl.ds(0, rows), :] = (jax.nn.silu(gate) * up).astype(
                h_ref.dtype)

        _at_half(rows_ref[i], x_ref.shape[0], products)


def _down_kernel(expert_ref, live_ref, row_ref, rows_ref, token_ref,
                 weight_ref, h_ref, w2_ref, o_ref, y_ref):
    """One grid step of ``nns_grouped_down``: a tile's ``h`` rows
    through a block of columns of its expert's ``w2``, and each of the
    expert's own rows, weighted, added into its token's row of that
    block of the float32 sum, which stays in VMEM through the tiles."""
    from jax.experimental import pallas as pl

    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        _zero(o_ref)

    @pl.when(i < live_ref[0])
    def _():
        def product(rows: int):
            y_ref[pl.ds(0, rows), :] = jnp.dot(
                h_ref[pl.ds(0, rows), :], w2_ref[...],
                preferred_element_type=jnp.float32)

        n = rows_ref[i]
        _at_half(n, h_ref.shape[0], product)
        _add_rows(o_ref, y_ref, token_ref, weight_ref, row_ref[i], n)


def _split_swiglu(xs, token, weight, expert, live, row, rows, w1, w3, w2, *,
                  tokens: int, tile: int, align: int, blocks, interpret: bool):
    """:func:`_aligned_swiglu`'s sum from two kernels, split at the
    activation so that neither holds a whole expert or the whole sum:
    ``nns_grouped_gate_up`` (grid: a block of ``f`` columns, then the
    walk's steps) writes a step's ``silu(x w1) * (x w3)`` rows as block
    ``i`` of ``h`` ``[tiles * tile, f]`` in ``xs``' dtype;
    ``nns_grouped_down`` (grid: a block of ``d`` columns, then the
    steps) multiplies them by ``w2`` and adds each served row, weighted,
    into a ``[tokens, dc]`` float32 block of the sum held in VMEM across
    the steps and written once. ``blocks``: ``(fb, dc)`` from
    :func:`_split_blocks`; ``interpret``: through the Pallas interpreter
    (off the TPU). The walk's steps past the live ones name the last
    live step's blocks, so nothing is fetched or written for them; an
    expert's tiles are consecutive, so each block of its matrices is
    fetched once a call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    d, f = w1.shape[1:]
    tiles = expert.shape[0]
    fb, dc = blocks

    def rows_in(j, i, expert, live, row, *_):
        return pl.multiple_of(row[i], align), 0

    def matrix(j, i, expert, *_):       # the step's expert's block ``j``
        return expert[i], 0, j

    def step(i, live):                  # past the live steps, the last one
        return jnp.minimum(i, jnp.maximum(live[0] - 1, 0))

    h = pl.pallas_call(
        _gate_up_kernel,
        out_shape=jax.ShapeDtypeStruct((tiles * tile, f), xs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(f // fb, tiles),
            in_specs=[pl.BlockSpec((pl.Element(tile), pl.Element(d)),
                                   rows_in),
                      pl.BlockSpec((None, d, fb), matrix),
                      pl.BlockSpec((None, d, fb), matrix)],
            out_specs=pl.BlockSpec(
                (tile, fb), lambda j, i, _, live, *__: (step(i, live), j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_gate_up_vmem(d, fb, tile, xs.dtype.itemsize)),
        interpret=interpret,
        name="nns_grouped_gate_up",
    )(expert, live, row, rows, xs, w1, w3)
    return pl.pallas_call(
        _down_kernel,
        out_shape=jax.ShapeDtypeStruct((tokens, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(d // dc, tiles),
            in_specs=[pl.BlockSpec(
                          (tile, f), lambda c, i, _, live, *__: (
                              step(i, live), 0)),
                      pl.BlockSpec((None, f, dc), matrix)],
            out_specs=pl.BlockSpec((tokens, dc), lambda c, i, *_: (0, c)),
            scratch_shapes=[pltpu.VMEM((tile, dc), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_down_vmem(tokens, f, dc, tile,
                                        xs.dtype.itemsize)),
        interpret=interpret,
        name="nns_grouped_down",
    )(expert, live, row, rows, token, weight, h, w2)


def _gate_up_vmem(d: int, fb: int, tile: int, itemsize: int) -> int:
    """The VMEM ``nns_grouped_gate_up`` asks for: two blocks each of
    ``w1`` and ``w3`` (``[d, fb]``), of a tile of rows and of its ``h``
    block, the float32 gate and up, and 16 MiB for Mosaic's own."""
    return 2 * (2 * d * fb + tile * d + tile * fb) * itemsize \
        + 2 * tile * fb * 4 + (16 << 20)


def _down_vmem(tokens: int, f: int, dc: int, tile: int,
               itemsize: int) -> int:
    """The VMEM ``nns_grouped_down`` asks for: two float32 sum blocks of
    ``tokens`` rows and ``dc`` columns (one fills while the other is
    written), two blocks each of ``w2`` (``[f, dc]``) and of a tile's
    ``h`` rows, a step's float32 rows, and 16 MiB for Mosaic's own."""
    return 2 * tokens * dc * 4 + 2 * (f * dc + tile * f) * itemsize \
        + tile * dc * 4 + (16 << 20)


def _split_blocks(tokens: int, d: int, f: int, tile: int, itemsize: int):
    """``(fb, dc)``: the widest blocks of ``f`` and of ``d`` columns, each
    the whole width or a multiple of 128 lanes that divides it, whose
    kernels' asks (:func:`_gate_up_vmem`, :func:`_down_vmem`) fit a
    core's VMEM."""
    def widest(width, fits):
        for c in range(width, 0, -1):
            if width % c == 0 and (c == width or c % 128 == 0) and fits(c):
                return c
        raise ValueError(f"no block of {width} columns fits VMEM beside "
                         f"{tokens} tokens' sum")
    return (widest(f, lambda fb: _gate_up_vmem(d, fb, tile, itemsize)
                   <= _VMEM),
            widest(d, lambda dc: _down_vmem(tokens, f, dc, tile, itemsize)
                   <= _VMEM))


def _pass_pairs(pairs: int, held: int, router: int, tile: int,
                align: int) -> int:
    """The sorted rows one pass of the split form takes: twice the pairs
    an even routing serves here (``pairs x held / router``), at most
    every pair, and within what the down kernel's SMEM holds (a token
    and a weight a slot, an expert, a row and a row count a step,
    64 KiB spare)."""
    smem = (_SMEM - (64 << 10) - 8 * (held * (align - 1) + tile)
            - 12 * held) * tile // (8 * tile + 12)
    return max(1, min(pairs, 2 * -(-pairs * held // router), smem))


def _by_split(x, order, counts, pair_weight, w1, w3, w2, *, tile: int,
              router: int):
    """:func:`grouped_swiglu` through ``nns_grouped_gate_up`` and
    ``nns_grouped_down``, in passes of :func:`_pass_pairs` sorted rows
    (module docstring)."""
    t, d = x.shape
    pairs = order.shape[0]
    align = math.gcd(tile, 32 // x.dtype.itemsize)
    n = _pass_pairs(pairs, counts.shape[0], router, tile, align)
    one_pass = functools.partial(
        _split_pass, x, order, counts, pair_weight, w1, w3, w2, n=n,
        tile=tile, align=align,
        blocks=_split_blocks(t, d, w1.shape[2], tile, x.dtype.itemsize),
        interpret=jax.default_backend() != "tpu")
    out = one_pass(jnp.int32(0))
    if n < pairs:
        # a routing that serves more than a pass holds here takes the
        # same kernels over the next rows, as many passes as it needs
        out = jax.lax.while_loop(
            lambda c: c[0] * n < jnp.sum(counts),
            lambda c: (c[0] + 1, c[1] + one_pass(c[0] * n)),
            (jnp.int32(1), out))[1]
    return out


@functools.partial(jax.jit, static_argnames=("n", "tile", "align", "blocks",
                                             "interpret"))
def _split_pass(x, order, counts, pair_weight, w1, w3, w2, lo, *, n: int,
                tile: int, align: int, blocks, interpret: bool):
    """The split form's sum over the pairs in sorted rows ``[lo, lo +
    n)``. A program of its own, so that a model's expert layers of one
    shape, and a layer's passes, are traced and lowered once."""
    t = x.shape[0]
    pairs, experts = order.shape[0], counts.shape[0]
    k = pairs // t
    slots = n + experts * (align - 1) + tile
    ends = jnp.cumsum(counts)
    with jax.named_scope("rows"):
        # each held expert's rows in this pass, laid out as _by_kernel's
        # at ``align``; every sorted row outside the pass is keyed past
        # the slots. The pair and the weight in each slot by sorts, no
        # gather of scalars
        served = jnp.clip(ends - lo, 0, n) - jnp.clip(ends - counts - lo, 0, n)
        to, gaps = _spread(served, n, align, slots)
        to = jax.lax.dynamic_update_slice(
            jnp.full((pairs + n,), slots, jnp.int32), to, (lo,))[:pairs]
        _, to = jax.lax.sort((order.astype(jnp.int32), to), num_keys=1)
        _, pair, weight = jax.lax.sort((
            jnp.concatenate([to, gaps]),
            jnp.concatenate([jnp.arange(pairs, dtype=jnp.int32),
                             jnp.zeros_like(gaps)]),
            jnp.concatenate([pair_weight.reshape(-1).astype(jnp.float32),
                             jnp.zeros(gaps.shape, jnp.float32)])),
            num_keys=1)
        token = pair[:slots] // k
        xs = x[token]
        # the most tiles a pass takes
        walk = _walk(served, -(-n // tile) + experts - 1, tile, align)
    return _split_swiglu(xs, token, weight[:slots], *walk, w1, w3, w2,
                         tokens=t, tile=tile, align=align, blocks=blocks,
                         interpret=interpret)
