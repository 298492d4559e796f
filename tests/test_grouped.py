"""``ops/grouped.py``, the grouped expert product, at tiny sizes on the
CPU (the kernel ``nns_grouped_swiglu`` through the Pallas interpreter):
each form against the dense form (every held expert over every token,
weighted by the token's weight for it or 0) and against the other; the
kernel's grid, layout, ring and what one call holds; and which form the
share a decoder's expert layer holds of its router takes."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nnstreamer_tpu.filters import prepare
from nnstreamer_tpu.models import zoo
from nnstreamer_tpu.ops import grouped
from nnstreamer_tpu.ops.grouped import (group_by_expert, grouped_swiglu,
                                        takes_kernel, takes_split)


@pytest.mark.parametrize("tile", [1, 8, 64])
@pytest.mark.parametrize("held_first,held", [(0, 6), (3, 2), (0, 1)])
def test_grouped_swiglu_is_exact(tile, held_first, held):
    """The split form (by name, at any share: 6, 2 and 1 of a router of
    7, the smaller two in more than one pass) against every expert
    applied to every token and masked: no pair of a held expert
    dropped, none of another expert served, an expert nobody chose
    costs nothing and breaks nothing."""
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
    got = jax.jit(lambda *a: grouped._by_split(*a, tile=tile, router=router))(
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


@pytest.mark.parametrize("form", ["sorted", "split_one_pass", "split"])
@pytest.mark.parametrize("tile,dtype,tol", [(8, jnp.float32, 1e-3),
                                            (32, jnp.float32, 1e-3),
                                            (16, jnp.bfloat16, 0.15)])
@pytest.mark.parametrize("routing", [
    "gap_middle", "gap_first", "gap_last", "many_tiles", "off_tile",
    "one_expert", "ragged_end"])
def test_grouped_swiglu_holds_the_whole_router(routing, tile, dtype, tol,
                                               form):
    """``held_count`` equal to the router's width: every one of the ``T
    x K`` pairs is served here, none goes to the tail, and the result is
    the dense form's (every expert over every token, weighted by the
    token's weight for it or 0), from the kernel's form (the whole
    router held: the rows gathered once, ``nns_grouped_swiglu`` through
    the interpreter) and from the split form (by name: in one pass, and
    with its passes sized as if the experts were a quarter of the
    router, so that every pair held here takes two) alike.
    The routings are those a walk over row tiles can get wrong: experts
    that serve nobody first, last and two in the middle, an
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
    if form == "split":
        assert grouped._pass_pairs(t * k, router, 4 * router, tile, 8) \
            == t * k // 2
    form = {"sorted": functools.partial(grouped_swiglu, router=router),
            "split_one_pass": functools.partial(grouped._by_split,
                                                router=router),
            "split": functools.partial(grouped._by_split,
                                       router=4 * router)}[form]
    got = jax.jit(lambda *a: form(*a, tile=tile))(
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
    expert's ``j``-th tile on, inside the buffer; of them it adds
    ``min(tile, count - j tile)`` into the sum, its expert's own, and a
    step past the live ones adds none; the free slots are all the
    others."""
    from nnstreamer_tpu.ops.grouped import _spread, _walk, tiles_walked
    tile, align, rows, g = 8, 2, sum(counts), len(counts)
    tiles = -(-rows // tile) + g - 1
    expert, live, row, added = (np.asarray(a) for a in _walk(
        jnp.asarray(counts, jnp.int32), tiles, tile, align))
    assert expert.shape == row.shape == added.shape == (tiles,)
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
    # the way out: every pair added once, by a step of its own expert
    assert added[:live].tolist() == [min(tile, c - j * tile)
                                     for c, n in zip(counts, of)
                                     for j in range(n)]
    assert (added[live:] == 0).all() and (added[:live] > 0).all()
    assert np.bincount(expert[:live], added[:live], g).tolist() == counts


def _whole_router_case(rng, dtype, t=48, k=2, router=6, d=16, f=24,
                       counts=None):
    """Operands on which every order of accumulation gives the same
    bits: ``x``, ``w1``, ``w3`` small integers (gate and up are exact),
    each column of an expert's ``w2`` one signed power of two (the last
    product's sums have one term), the pairs' weights powers of two (a
    weighted row is exact, fused into its add or not), so two
    statements of one product differ only where they serve a row
    otherwise. ``counts``: the pairs of each expert instead, a token
    choosing one (``t = sum(counts)``, ``k = 1``)."""
    if counts is not None:
        router, t, k = len(counts), sum(counts), 1
    x = rng.integers(-2, 3, (t, d)).astype(np.float32)
    choice = np.stack([rng.permutation(router)[:k] for _ in range(t)]
                      ).astype(np.int32) if counts is None else \
        rng.permutation(np.repeat(np.arange(router), counts)
                        )[:, None].astype(np.int32)
    weight = rng.choice([.25, .5, 1., 2.], (t, k)).astype(np.float32)
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


# routings of one pair a token over tiles of 8 whose experts' last tiles
# hold exactly half the rows, one row more, one row, and experts whose
# every row lies in one half tile; with the last tiles the kernel takes
# at half (``half_tiles``)
_HALF_TILE_ROUTINGS = {
    "last_at_half": ([12, 20, 0, 4, 28], 4),
    "half_and_one": ([13, 5, 0, 21, 8], 0),
    "one_row": ([9, 1, 17, 0, 25], 4),
    "in_one_half": ([0, 3, 0, 2, 4, 0, 16], 3),
}


def test_grouped_kernel_reads_no_row_of_a_tiles_tail():
    """A step multiplies a whole tile, or its first half where its
    expert's last rows fit there: past its expert's rows the slots left
    free by the alignment (they hold pair 0's row, a real one) and the
    next experts' rows, on the wrong matrices, stored where nobody
    reads. With pair 0's token poisoned (NaN), every other token's
    result is what it was, bit for bit, though the tiles hold the NaN
    row many times over; on the routings whose last tiles fall at half
    a tile, one row over, one row, and inside one half tile, the kernel
    gives the split form's result bit for bit."""
    from nnstreamer_tpu.ops.grouped import half_tiles, tiles_walked
    tile = 8
    cases = [(None, None)] + list(_HALF_TILE_ROUTINGS.values())
    for i, (counts_want, halves) in enumerate(cases):
        x, order, counts, *rest = _whole_router_case(
            np.random.default_rng(9 + i), jnp.float32, counts=counts_want)
        t, k = x.shape[0], order.shape[0] // x.shape[0]
        router = counts.shape[0]
        if counts_want is None:
            # more than a tile's worth of tail rows in the live tiles
            assert int(tiles_walked(counts, tile)) * tile - t * k >= 8
        else:
            assert counts.tolist() == counts_want
            assert int(half_tiles(counts, tile)) == halves
        run = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile, router=router))
        clean = np.asarray(run(x, order, counts, *rest))
        dirty = np.asarray(run(x.at[0].set(jnp.nan), order, counts, *rest))
        assert np.isnan(dirty[0]).all() and np.isfinite(clean).all()
        np.testing.assert_array_equal(dirty[1:], clean[1:])
        split = jax.jit(lambda *a: grouped._by_split(
            *a, tile=tile, router=router))(x, order, counts, *rest)
        np.testing.assert_array_equal(clean, np.asarray(split))


@pytest.mark.parametrize("counts", [
    [0, 5, 0, 0, 9, 1, 0, 17, 0],       # gaps first, inside and last
    [3, 0, 0, 0, 0, 0, 0, 2],           # two experts, six between them
    [0, 0, 0, 40, 0],                   # one expert
    [8, 8, 8, 8, 8, 8, 8, 8],           # more experts than any ring
], ids=["gaps", "far_apart", "one_expert", "no_gap"])
@pytest.mark.parametrize("depth", [2, 3, 4])
def test_grouped_kernel_fetches_the_experts_in_the_walks_order(
        monkeypatch, counts, depth):
    """The ring of matrices (``_ring``) lists the experts in the order
    ``_walk``'s steps meet them, each once and none that serves nobody,
    and the kernel run as the chip runs it (Pallas' TPU interpreter: a
    copy lands only when it is waited for, memory nobody wrote reads
    NaN, races are looked for) gives the split form's result bit for
    bit at every depth of the ring."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    from jax.experimental.pallas import tpu as pltpu
    tile = 8
    rows = sum(counts)
    expert, live, _, _ = (np.asarray(a) for a in grouped._walk(
        jnp.asarray(counts, jnp.int32), -(-rows // tile) + len(counts) - 1,
        tile, 2))
    walked = expert[:int(live[0])]
    met = walked[np.r_[True, walked[1:] != walked[:-1]]].tolist()
    ring = np.asarray(grouped._ring(jnp.asarray(counts, jnp.int32), depth))
    assert ring.shape == (len(counts) + depth - 1,)
    assert ring.tolist() == met + [-1] * (len(ring) - len(met))
    assert met == [e for e, c in enumerate(counts) if c]
    monkeypatch.setattr(grouped, "_DEEPEST", depth)
    args = _whole_router_case(np.random.default_rng(depth), jnp.float32,
                              counts=counts)
    assert grouped._depth(rows, 16, 24, tile, 4) == depth
    with pltpu.force_tpu_interpret_mode(pltpu.InterpretParams(
            detect_races=True, dma_execution_mode="on_wait")):
        got = jax.jit(lambda *a: grouped.grouped_swiglu(
            *a, tile=tile, router=len(counts)))(*args)
        got = np.asarray(got)
    assert not interpret_pallas_call.races.races_found
    split = jax.jit(lambda *a: grouped._by_split(
        *a, tile=tile, router=len(counts)))(*args)
    np.testing.assert_array_equal(got, np.asarray(split))


@pytest.mark.parametrize("t,d,depth,most", [
    (8192, 2304, 2, 8192), (4096, 2048, 3, 4096), (2048, 2304, 3, 4096),
    (9159, 2304, 2, 9159)],
    ids=["kimi_linear", "trinity", "kimi_linear_s2048", "kimi_linear_most"])
def test_the_ring_is_as_deep_as_vmem_holds(t, d, depth, most):
    """At the cells' widths (experts of ``d`` x 1024, 8 a token, 128 held,
    tiles of 256, bfloat16): the ring holds as many sets of matrices as
    VMEM holds beside the sum, a step's float32 rows and a tile of rows
    twice over, at least two and at most ``_DEEPEST`` (2 at Kimi-Linear's
    8192 tokens of 2304, where a third set would split the call; 3 at
    Trinity's 4096 of 2048, where six would fit, and at a quarter of
    Kimi-Linear's tokens), within the chip's VMEM at each; and one call
    still takes the cells' tokens."""
    got = grouped._depth(t, d, 1024, 256, 2)
    assert got == depth and 2 <= got <= grouped._DEEPEST
    assert grouped._vmem_asked(t, d, 1024, 256, 2, got) <= grouped._VMEM
    assert grouped._vmem_asked(t, d, 1024, 256, 2, got + 1) \
        > grouped._VMEM or got == grouped._DEEPEST
    assert grouped._most_tokens(d, 1024, 8, 128, 256, 16, 2) >= most


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
        # and a single row: both sides of where a step takes half a tile
        half = tile // 2
        pairs = [2 * tile + half, tile + half + 1, tile, 1]
        ids = np.repeat(inside, pairs)
        ids = np.concatenate([ids, np.full(t - len(ids), outside[0])])
        return rng.permutation(ids)[:, None]
    if case == "half_tiles":
        # k == 1; experts whose every row lies in one half tile (half of
        # it and one row less), one a row over a tile, one whose last
        # tile is half full
        half = tile // 2
        pairs = [half, tile + 1, 2 * tile + half, half - 1]
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
    # the kernel (half the router held), its steps at half a tile
    ("kernel_half_full", 64, 1, 8, jnp.float32, 1e-5),
    ("kernel_half_tiles", 64, 1, 8, jnp.float32, 1e-5),
    ("kernel_bfloat16_half_full", 96, 1, 16, jnp.bfloat16, 2e-2),
    ("kernel_bfloat16_half_tiles", 96, 1, 16, jnp.bfloat16, 2e-2),
])
def test_grouped_swiglu_serves_every_routing(case, t, k, tile, dtype, tol):
    """The split form (whole tiles, and last tiles at half the rows:
    4 of a router of 9 held) against each pair computed alone: an expert of
    several tiles, one that serves nobody between two that do, every
    pair on one expert, no pair of a held expert (exactly 0), last
    tiles on both sides of half full, an odd tile, bfloat16. The kernel
    (``kernel_``: 4 of a router of 8 held) on last tiles at half full,
    a row over, one row, and experts whose every row lies in one half
    tile: against each pair alone, and the split form's result (by
    name) bit for bit (a token's one pair, its products on a tile or a
    half tile in both)."""
    kernel = case.startswith("kernel_")
    held_first, held, router, d, f = 2, 4, 8 if kernel else 9, 16, 24
    rng = np.random.default_rng(len(case))
    case = case.removeprefix("kernel_").removeprefix("bfloat16_")
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
                    [tile // 2, tile // 2 + 1, 0, 1])).all(),
                "half_tiles": (counts == jnp.asarray(
                    [tile // 2, tile + 1, 2 * tile + tile // 2,
                     tile // 2 - 1])).all()}.get(case, True)
    assert bool(expected), counts
    assert takes_kernel(held, router) == kernel
    got = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile, router=router))(
        x, order, counts, weight, w1, w3, w2)
    if kernel:
        split = jax.jit(lambda *a: grouped._by_split(
            *a, tile=tile, router=router))(
                x, order, counts, weight, w1, w3, w2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(split))
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


def _whole_router_choice(case, rng, t, k, router, tile):
    """``[t, k]`` expert ids over the whole router, a token's all
    different, for a way-out edge of the grouped kernel (below)."""
    for _ in range(100):
        choice = np.stack([rng.permutation(router)[:k] for _ in range(t)])
        if case == "an_expert_nobody_chose":
            for row in choice:
                row[row == 2] = rng.choice(np.setdiff1d(np.arange(router),
                                                        [2, *row]))
        counts = np.bincount(choice.ravel(), minlength=router)
        if case == "ends_mid_tile" and (counts[0] % tile == 0
                                        or not counts[1]):
            continue
        if case == "a_tile_in_the_slack" and (counts[-1] % tile == 0
                                              or router - 1 not in choice[0]):
            continue
        return choice
    raise AssertionError(case)


@pytest.mark.parametrize("case", ["mixed", "an_expert_nobody_chose",
                                  "ends_mid_tile", "a_tile_in_the_slack"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-3),
                                       (jnp.bfloat16, 0.15)],
                         ids=["float32", "bfloat16"])
def test_the_whole_router_adds_each_pair_once(case, dtype, tol):
    """The whole router held (Trinity's share): every one of a token's
    ``K`` pairs is served here and added, weighted, into its row by the
    kernel, once. The way out's edges: an expert nobody chose, expert
    0's run ending inside a tile with expert 1's rows behind it (not
    added), the last expert's tile reaching the slack slots that hold
    pair 0 (token 0's, which the last expert serves: counted once).
    Against the dense form and the split form (by name)."""
    rng = np.random.default_rng(41)
    t, d, f, k, router, tile = 48, 16, 24, 4, 8, 32
    choice = _whole_router_choice(case, rng, t, k, router, tile)
    x = rng.standard_normal((t, d)).astype(np.float32)
    weight = rng.random((t, k)).astype(np.float32)
    w1, w3 = (rng.standard_normal((router, d, f)).astype(np.float32)
              for _ in range(2))
    w2 = rng.standard_normal((router, f, d)).astype(np.float32)
    order, counts = group_by_expert(jnp.asarray(choice, jnp.int32), 0, router)
    assert (case != "an_expert_nobody_chose") == bool(int(counts[2]))
    args = (jnp.asarray(x, dtype), order, counts, weight,
            *(jnp.asarray(w, dtype) for w in (w1, w3, w2)))
    got = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile, router=router))(
        *args)
    split = jax.jit(lambda *a: grouped._by_split(
        *a, tile=tile, router=router))(*args)
    dense = np.zeros((t, router), np.float32)
    np.add.at(dense, (np.arange(t)[:, None], choice), weight)
    every = jnp.einsum("tef,efd->ted", jax.nn.silu(
        jnp.einsum("td,edf->tef", x, w1)) * jnp.einsum("td,edf->tef", x, w3),
        w2)
    want = jnp.einsum("ted,te->td", every, dense)
    atol = tol * (1 + 9 * (dtype == jnp.bfloat16))
    np.testing.assert_allclose(got, want, atol=atol, rtol=tol)
    np.testing.assert_allclose(got, split, atol=atol, rtol=tol)


def _routing(case, rng, t, k, held_first, held, router):
    """``[t, k]`` expert ids, a token's all different."""
    inside = np.arange(held_first, held_first + held)
    outside = np.setdiff1d(np.arange(router), inside)
    if case == "every_pair_absent":
        return np.stack([rng.permutation(outside)[:k] for _ in range(t)])
    if case in ("every_pair_here", "a_tile_in_the_slack"):
        return np.stack([rng.permutation(inside)[:k] for _ in range(t)])
    choice = np.stack([rng.permutation(router)[:k] for _ in range(t)])
    if case == "a_token_all_here":
        choice[5] = rng.permutation(inside)[:k]
    if case == "a_token_none_here":
        choice[5] = rng.permutation(outside)[:k]
    if case == "an_expert_nobody_chose":
        choice[choice == held_first + 2] = outside[0]
        # a token's ids stay different: drop a doubled one on another
        for row in choice:
            seen = set()
            for c in range(k):
                while row[c] in seen:
                    row[c] = rng.choice(np.setdiff1d(
                        np.arange(router), [held_first + 2, *seen]))
                seen.add(row[c])
    return choice


@pytest.mark.parametrize("case", [
    "mixed", "an_expert_nobody_chose", "every_pair_absent", "every_pair_here",
    "a_token_all_here", "a_token_none_here", "ends_mid_tile",
    "a_tile_in_the_slack", "runs_of_tokens"])
@pytest.mark.parametrize("held_first,dtype,tol", [
    (0, jnp.float32, 1e-3), (8, jnp.float32, 1e-3), (8, jnp.bfloat16, 0.15)],
    ids=["rank0", "rank1", "rank1_bfloat16"])
def test_the_kernels_path_serves_a_share(monkeypatch, case, held_first,
                                         dtype, tol):
    """Half a router held (8 of 16): the sorted buffer holds the pairs
    of the experts held elsewhere behind the last held expert's, where
    the kernel's walk never begins a step, and on the way out they
    count as nothing: the kernel adds each served pair, weighted, into
    its token's row, and no other row. Against the split form (by
    name, on the same arguments) and against the dense form (every
    held expert over every token, weighted by the token's weight for it
    or 0). The way out's edges: a token whose pairs are all here, one
    with none here (its row exactly 0), an expert whose run ends inside
    a tile with the next expert's rows behind it, the last expert's
    tile reaching the slack slots (which hold pair 0, token 0's: counted
    once), an expert nobody chose; and a sum of more tokens than the
    chip's VMEM holds (the module's capacity steered down to 40 tokens'
    sum): three calls on runs of 32 tokens, each run grouped anew."""
    rng = np.random.default_rng(3)
    t, d, f, k, router, held, tile = 96, 16, 24, 4, 16, 8, 32
    assert takes_kernel(held, router)
    x = rng.standard_normal((t, d)).astype(np.float32)
    choice = _routing(case, rng, t, k, held_first, held, router
                      ).astype(np.int32)
    weight = rng.random((t, k)).astype(np.float32)
    w1, w3 = (rng.standard_normal((held, d, f)).astype(np.float32)
              for _ in range(2))
    w2 = rng.standard_normal((held, f, d)).astype(np.float32)
    order, counts = group_by_expert(jnp.asarray(choice), held_first, held)
    if case == "every_pair_absent":
        assert int(counts.sum()) == 0
    if case == "every_pair_here":
        assert int(counts.sum()) == t * k
    if case == "an_expert_nobody_chose":
        assert int(counts[2]) == 0 and int(counts.sum()) > 0
    if case == "ends_mid_tile":
        # an expert's last tile holds the next expert's first rows
        assert any(c % tile and n for c, n in zip(counts[:-1].tolist(),
                                                  counts[1:].tolist()))
    if case == "a_tile_in_the_slack":
        # nothing is held elsewhere: the last expert's last tile runs
        # into the free slots behind it, and token 0 is served here
        assert int(counts.sum()) == t * k and int(counts[-1]) % tile
    args = (jnp.asarray(x, dtype), order, counts, weight,
            *(jnp.asarray(w, dtype) for w in (w1, w3, w2)))
    split = jax.jit(lambda *a: grouped._by_split(
        *a, tile=tile, router=router))(*args)
    if case == "runs_of_tokens":
        monkeypatch.setattr(grouped, "_VMEM", grouped._vmem_asked(
            40, d, f, tile, jnp.dtype(dtype).itemsize))
    got = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile, router=router))(
        *args)
    assert _kernel_calls(lambda *a: grouped_swiglu(
        *a, tile=tile, router=router), *args) \
        == (3 if case == "runs_of_tokens" else 1)
    assert got.dtype == jnp.float32
    dense = np.zeros((t, router), np.float32)
    np.add.at(dense, (np.arange(t)[:, None], choice), weight)
    dense = dense[:, held_first:held_first + held]
    every = jnp.einsum("tef,efd->ted", jax.nn.silu(
        jnp.einsum("td,edf->tef", x, w1)) * jnp.einsum("td,edf->tef", x, w3),
        w2)
    want = jnp.einsum("ted,te->td", every, dense)
    atol = tol * (1 + 9 * (dtype == jnp.bfloat16))
    np.testing.assert_allclose(got, want, atol=atol, rtol=tol)
    np.testing.assert_allclose(got, split, atol=atol, rtol=tol)
    if case == "a_token_none_here":
        assert not np.asarray(got[5]).any()


def _eighth(case, rng, t, k, tile, held, router):
    """``[t, k]`` expert ids, a token's all different, for the first
    ``held`` of ``router`` experts held (an eighth of it), for the edges
    of the split form (below)."""
    inside, outside = np.arange(held), np.arange(held, router)
    if case == "every_pair_here":
        return np.stack([rng.permutation(inside)[:k] for _ in range(t)])
    if case == "one_expert":
        # every pair of a held expert on expert 1
        return np.stack([np.r_[1, rng.permutation(outside)[:k - 1]]
                         for _ in range(t)])
    if case in ("tail_at_half", "tail_over_half"):
        # k == 1; an expert of two tiles and a half, one of a tile and
        # a half (and one row over), one whole tile, one single row
        half = tile // 2 + (case == "tail_over_half")
        ids = np.repeat(inside[:4], [2 * tile + half, tile + half, tile, 1])
        ids = np.concatenate([ids, rng.choice(outside, t - len(ids))])
        return rng.permutation(ids)[:, None]
    bias = {"skewed": np.r_[2.0, np.zeros(router - 1)]}.get(
        case, np.zeros(router))
    choice = np.argsort(-(rng.gumbel(size=(t, router)) + bias), axis=1)[:, :k]
    if case == "an_expert_nobody_chose":
        # expert 2 chosen by nobody: a token's pick of it moved to the
        # next free expert held elsewhere
        for row in choice:
            if 2 in row:
                row[row == 2] = np.setdiff1d(outside, row)[0]
    return choice


@pytest.mark.parametrize("case", [
    "even", "skewed", "an_expert_nobody_chose", "tail_at_half",
    "tail_over_half", "one_expert", "every_pair_here"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_split_form_serves_an_eighth(case, dtype, tol):
    """4 of a router of 32 held, as dots3's 32 of 256: ``grouped_swiglu``
    takes the split form (``nns_grouped_gate_up``, ``nns_grouped_down``
    through the interpreter) and serves every pair of a held expert,
    against each pair computed alone in float32 with ``silu(gate) * up``
    rounded once to the operands' dtype, and against the whole-router
    kernel's form by name on the same arguments (the same products and
    adds in the same order: to the bit). The routings: even, one expert
    far the busiest, an expert nobody chose, last tiles at half a tile
    and one row over, every pair on one expert, and every pair held
    here, four times what one pass holds: four passes, none dropped."""
    rng = np.random.default_rng(len(case))
    tile, held, router, d, f = 8, 4, 32, 16, 24
    t, k = (192, 1) if case.startswith("tail") else (64, 4)
    choice = _eighth(case, rng, t, k, tile, held, router).astype(np.int32)
    x = jnp.asarray(rng.standard_normal((t, d)), dtype)
    weight = jnp.asarray(rng.random((t, k)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((held, d, f)) * d ** -0.5,
                          dtype) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held, f, d)) * f ** -0.5, dtype)
    order, counts = group_by_expert(jnp.asarray(choice), 0, held)
    n = grouped._pass_pairs(t * k, held, router, tile, 8)
    served = np.asarray(counts)
    expected = {
        "skewed": served[0] > 4 * served[1:].max(),
        "an_expert_nobody_chose": served[2] == 0 and served.all(where=[
            True, True, False, True]),
        "tail_at_half": (served % tile == [tile // 2, tile // 2, 0, 1]).all(),
        "tail_over_half": (served % tile
                           == [tile // 2 + 1, tile // 2 + 1, 0, 1]).all(),
        "one_expert": served.tolist() == [0, t, 0, 0],
        "every_pair_here": served.sum() == t * k == 4 * n}.get(case, True)
    assert expected, served
    assert served.sum() <= n or case == "every_pair_here"
    assert takes_split(held, router)
    args = (x, order, counts, weight, w1, w3, w2)
    run = jax.jit(lambda *a: grouped_swiglu(*a, tile=tile, router=router))
    got = np.asarray(run(*args))
    # the first pass's two kernels, and the loop's over the passes after
    assert prepare.kernel_calls(jax.make_jaxpr(run)(*args)) == {
        "nns_grouped_gate_up": 2, "nns_grouped_down": 2}
    kernel = jax.jit(lambda *a: grouped._by_kernel(*a, tile=tile))(*args)
    if case != "every_pair_here":
        np.testing.assert_array_equal(got, np.asarray(kernel))
    want = np.zeros((t, d), np.float32)
    for tok, slot in np.argwhere(choice < held):
        e = choice[tok, slot]
        gate, up = (jnp.dot(x[tok], w[e], preferred_element_type=jnp.float32)
                    for w in (w1, w3))
        want[tok] += weight[tok, slot] * np.asarray(jnp.dot(
            (jax.nn.silu(gate) * up).astype(dtype), w2[e],
            preferred_element_type=jnp.float32))
    assert np.abs(want).max() > 0.1
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(got, want, atol=tol * scale)
    np.testing.assert_allclose(got, kernel, atol=tol * scale)


def _kernel_calls(fn, *args):
    return sum(e.primitive.name == "pallas_call"
               for e in jax.make_jaxpr(fn)(*args).eqns)


@pytest.mark.parametrize("t,d,calls", [
    (8192, 2304, 1), (9159, 2304, 1), (9160, 2304, 2), (16384, 2304, 2),
    (4096, 2048, 1), (14967, 512, 1), (20000, 512, 2)],
    ids=["kimi_linear", "kimi_linear_most", "kimi_linear_past_vmem",
         "kimi_linear_16k", "trinity", "narrow_most", "narrow_past_smem"])
def test_runs_of_tokens_fit_the_chip(t, d, calls):
    """The kernel's float32 sum of ``t`` tokens stays in VMEM beside an
    expert's matrices twice over (experts of ``d`` x 1024, 8 a token, 128
    held of 256, tiles of 256, bfloat16), and its slots' tokens and
    weights in SMEM: past either, the tokens go in runs that fit, a call
    each (VMEM binds at the Kimi-Linear cell's widths, SMEM at narrow
    ones)."""
    spec = jax.ShapeDtypeStruct
    args = (spec((t, d), jnp.bfloat16), spec((t * 8,), jnp.int32),
            spec((128,), jnp.int32), spec((t, 8), jnp.float32),
            spec((128, d, 1024), jnp.bfloat16),
            spec((128, d, 1024), jnp.bfloat16),
            spec((128, 1024, d), jnp.bfloat16))
    assert _kernel_calls(lambda *a: grouped_swiglu(
        *a, tile=256, router=256), *args) == calls


@pytest.mark.parametrize("t,d,f,k,held,router,fb,dc,n", [
    (8192, 5120, 1536, 8, 32, 256, 1536, 1280, 16384),
    (16384, 5120, 1536, 8, 32, 256, 1536, 640, 32768),
    (4096, 6144, 2048, 8, 16, 256, 2048, 2048, 4096),
    (16384, 6144, 2048, 8, 16, 256, 2048, 768, 16384),
    (4096, 6144, 2048, 12, 16, 768, 2048, 2048, 2048),
    (16384, 6144, 2048, 12, 16, 768, 2048, 768, 8192)],
    ids=["dots3", "dots3_16k", "glm5", "glm5_16k", "longcat", "longcat_16k"])
def test_the_split_kernels_fit_the_chip(t, d, f, k, held, router, fb, dc,
                                        n):
    """At the three cells' widths under half a router (experts of ``d``
    x ``f``, ``k`` a token, tiles of 256, bfloat16), on their tokens and
    on 16,384: ``nns_grouped_gate_up`` takes ``w1`` and ``w3`` whole
    beside a tile of rows, twice over, and ``nns_grouped_down`` the
    widest block of the float32 sum's columns that fits twice beside two
    blocks of ``w2``, each within a core's 128 MiB and a wider block
    past it; one pass holds twice the pairs an even routing serves here
    (dots3's 16,384 of its 65,536), its slots' tokens and weights and
    the walk's scalars within SMEM."""
    assert grouped._split_blocks(t, d, f, 256, 2) == (fb, dc)
    assert grouped._gate_up_vmem(d, fb, 256, 2) <= grouped._VMEM
    assert grouped._down_vmem(t, f, dc, 256, 2) <= grouped._VMEM
    wider = [c for c in range(dc + 128, d + 1, 128) if d % c == 0]
    assert all(grouped._down_vmem(t, f, c, 256, 2) > grouped._VMEM
               for c in wider)
    assert dc % 128 == 0 and d % dc == 0
    assert grouped._pass_pairs(t * k, held, router, 256, 16) == n \
        == 2 * t * k * held // router
    slots, steps = n + held * 15 + 256, n // 256 + held - 1
    assert 8 * slots + 12 * steps + (64 << 10) <= grouped._SMEM


def test_a_pass_holds_what_smem_holds():
    """Past what SMEM holds of its slots, a pass takes fewer rows and the
    routing more passes: at 1,048,576 tokens of dots3's share one pass
    holds 121,384 of the 2,097,152 pairs an even routing serves; and a
    pass never takes more than every pair."""
    n = grouped._pass_pairs(1 << 23, 32, 256, 256, 16)
    assert n == 121384
    slots, steps = n + 32 * 15 + 256, -(-n // 256) + 32 - 1
    assert 8 * slots + 12 * steps <= grouped._SMEM - (64 << 10) \
        < 8 * (slots + 256) + 12 * (steps + 1)
    assert grouped._pass_pairs(40, 2, 3, 8, 8) == 40


def test_which_shares_take_the_kernel():
    """At least half the router held: the buffer sorted by expert, sized
    for every pair, is at most twice the rows an even routing serves.
    Every other share takes the split form, and only those."""
    assert [takes_kernel(h, 16) for h in (16, 15, 8, 7, 4, 1)] \
        == [True, True, True, False, False, False]
    assert all(takes_split(h, r) != takes_kernel(h, r)
               for r in (16, 256, 768) for h in range(1, r + 1))
    assert takes_kernel(128, 256) and not takes_kernel(16, 256) \
        and not takes_kernel(16, 768) and takes_kernel(128, 128)


@pytest.mark.parametrize("name,share,layers,kernel", [
    ("glm_dsa", {}, 2, True),
    ("glm_dsa", {"held_first": "16", "held_count": "16"}, 2, True),
    ("glm_dsa", {"held_first": "0", "held_count": "15"}, 2, False),
    ("glm_dsa", {"held_first": "8", "held_count": "8"}, 2, False),
    ("glm_dsa", {"held_first": "0", "held_count": "2"}, 2, False),
    ("longcat", {}, 2, True),
    ("longcat", {"held_first": "8", "held_count": "16"}, 2, True),
    ("longcat", {"held_first": "0", "held_count": "15"}, 2, False),
    ("longcat", {"held_first": "4", "held_count": "4"}, 2, False),
    ("longcat", {"held_first": "0", "held_count": "1"}, 2, False),
    ("afmoe", {}, 3, True),
    ("afmoe", {"held_first": "0", "held_count": "16"}, 3, True),
    ("afmoe", {"held_first": "4", "held_count": "4"}, 3, False),
    ("afmoe", {"held_first": "0", "held_count": "15"}, 3, True),
    ("afmoe", {"held_first": "8", "held_count": "8"}, 3, True),
    ("afmoe", {"held_first": "0", "held_count": "7"}, 3, False),
    ("kimi_linear", {}, 4, True),
    ("kimi_linear", {"held_first": "8", "held_count": "8"}, 4, True),
    ("kimi_linear", {"held_first": "0", "held_count": "7"}, 4, False),
    ("kimi_linear", {"held_first": "12", "held_count": "4"}, 4, False),
    ("dots3_note", {}, 4, True),
    ("dots3_note", {"held_first": "0", "held_count": "4"}, 4, False),
], ids=["glm_dsa-default", "glm_dsa-a_half", "glm_dsa-under_a_half",
        "glm_dsa-a_quarter", "glm_dsa-a_sixteenth", "longcat-default",
        "longcat-a_half", "longcat-under_a_half", "longcat-an_eighth",
        "longcat-one_of_32", "afmoe-default", "afmoe-all_sixteen",
        "afmoe-a_quarter", "afmoe-all_but_one", "afmoe-a_half",
        "afmoe-under_a_half", "kimi_linear-default", "kimi_linear-a_half",
        "kimi_linear-under_a_half", "kimi_linear-a_quarter",
        "dots3_note-default", "dots3_note-an_eighth"])
def test_the_share_picks_the_form(name, share, layers, kernel):
    """``nns_grouped_swiglu`` once an expert layer of the traced zoo
    program where the experts held are at least half the router (the
    router's width read off the layer's gate, LongCat's identity outputs
    among them: 32 wide at 24 real): Trinity's whole router and
    Kimi-Linear's half. Under a half (GLM-5's sixteenth, LongCat's 16
    of 768, dots3's eighth among them) the split form instead:
    ``nns_grouped_gate_up`` and ``nns_grouped_down`` twice an expert
    layer, the first pass's and the loop's over the passes a routing
    past one pass takes. What the layer observes of itself, for every
    decoder alike; no option."""
    apply_fn, params, in_info, _ = zoo.build(name, seq="64", seed="3",
                                             **share)
    closed = jax.make_jaxpr(apply_fn)(
        params, jnp.zeros(in_info[0].shape, jnp.int32))
    calls = prepare.kernel_calls(closed)
    assert calls.get("nns_grouped_swiglu", 0) == (layers if kernel else 0)
    assert calls.get("nns_grouped_gate_up", 0) \
        == calls.get("nns_grouped_down", 0) == (0 if kernel else 2 * layers)
