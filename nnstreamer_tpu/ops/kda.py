"""Chunked gated delta rule: a linear attention whose state decays a
channel at a time and is corrected by the delta rule (Kimi Delta
Attention's recurrence, arXiv 2510.26692; ``fla/ops/kda``).

A head keeps a state ``S`` [dk, dv] float32, ``S_0 = 0``, and for token
``t`` with query ``q_t`` and key ``k_t`` [dk] (``k_t`` of unit length,
``q_t`` of length ``dk^-0.5``: the l2 norms of the layer, taken inside
the kernels as ``fla``'s ``use_qk_l2norm_in_kernel`` does, so that what
the projections wrote is read once and no normalised copy goes through
HBM), value ``v_t`` [dv], log-decay ``a_t`` [dk] (``<= 0``) and
``beta_t`` in (0, 1):

    S_t = (I - beta_t k_t k_t^T) diag(exp(a_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

(decay first, then the delta rule on the decayed state). Token by token
that is ``S`` dependent steps a head. :func:`kda_chunked` computes the
same ``o``, exactly, ``chunk`` tokens at a time. Inside a chunk that
starts from state ``S0``, with ``c_i`` the running sum of ``a`` from the
chunk's first row to row ``i`` and ``u_i = beta_i (v_i - D_i^T k_i)``
(``D_i`` the decayed state row ``i`` meets), unrolling the recurrence
gives

    S_i = diag(exp(c_i)) S0 + sum over j <= i of (k_j * exp(c_i - c_j)) u_j^T
    (I + diag(beta) A) U = diag(beta) (V - (K * exp(c)) S0)
    o_i = (q_i * exp(c_i))^T S0 + sum over j <= i of B_ij u_j
    A_ij = sum_d k_i k_j exp(c_i - c_j)  (j < i),
    B_ij = sum_d q_i k_j exp(c_i - c_j)  (j <= i)

so a chunk is two ``chunk x chunk`` matrices, one unit-lower-triangular
system (its inverse ``T`` is formed exactly, by substitution a row at a
time; no series in the nilpotent part, whose terms can cancel
catastrophically) and three products with the state.

Two Pallas TPU kernels under ``jax.named_scope("nns_kda_chunk")``:
``nns_kda_chunk_intra`` makes ``T`` and ``B`` of every chunk (no state
in it) and ``nns_kda_chunk_state`` walks the heads' chunks in turn with
the states in VMEM: ``S0 -> U, o, S0'``. Between HBM and the chip go q,
k and the decays twice, v and beta once, the two matrices out and in
(half of q's bytes each) and o out; the decays' running sums, their
exponentials, the normalised q and k, ``K exp(c)``, ``M``, ``W`` and
``U`` never leave VMEM. Compiled by Mosaic on a TPU, through the Pallas
interpreter elsewhere (how the CPU tests run it).

**A grid step holds more than one independent problem** (PR 39). Each
kernel was bound by a unit that one serial problem leaves waiting, and
both now read their packing off the shapes, in :func:`_packing` and
nowhere else:

- *The state kernel* takes the same block of rows of ``heads`` heads a
  step, the largest of 4, 2 and 1 that divides ``H``, ``STEP`` rows in
  all (at most ``ROWS`` of a head, whole chunks, dividing ``S``), and
  its loop takes chunk ``n`` of every head before chunk ``n + 1`` of
  any. A chunk is two dependent products on the state; alone, a head
  waited on the MXU's results for half of its time (2,688 bundles a
  step in the compiler's schedule, 5,713 cycles measured).
- *The first kernel* keeps the ``M`` of a head's chunks in VMEM until
  ``LANES`` of them are there (every ``turn`` steps of ``ROWS`` rows;
  all 128 chunks of a head of 8192 tokens at once) and inverts them
  side by side, **a lane a chunk** (:func:`_solve_by_lanes`). The
  substitution it replaces took a rank-one update a column, and the
  column's broadcast along the lanes is three XLU instructions a
  register (``vset.pattern``, ``vperm``, ``vpop.permute``): 2,248
  broadcasts a step of 8 chunks kept the three XLUs busy for two thirds
  of the kernel. With a chunk a lane ``M[i, j]`` of 128 chunks is one
  row of a register, its broadcast goes along the sublanes, and every
  multiply works on a full register: 6.4 k register updates a head
  where there were 35.8 k half-empty ones, none through the XLU. The
  rows reach that layout by swapped axes (``[g, i, j] -> [i, g, j]``
  as a step stores its ``M``, back as ``T`` is written) and one
  ``[128, 64]`` transpose a row each way.

Read on the chip at 32 heads x 8192 tokens of 128, chunks of 64, ms a
layer, each kernel alone in a program of its own (which adds the copies
of beta and of the matrices that the layer's program does not have:
about 0.4 ms a kernel; PERF.md §6, PR 38 and PR 39). *The whole op:* the
same mathematics as ``jax.numpy`` with a ``lax.scan`` over the chunks
27.9; the two kernels with the inverse left to XLA between them 21.3;
the inverse inside the first kernel 6.9, over the tiles of 8 rows that
its triangle leaves non-zero 6.1 (PR 38's: 6.19 in this PR's call); as
it stands **2.79** (1.86 + 1.46 for the kernels alone; in the layer's
program 1.41 + 1.05 where they were 3.88 + 1.95).
*The state kernel*, heads a step x rows of a head: 1 x 512 (PR 38's)
2.38, 2 x 512 1.62, 2 x 256 1.66, **4 x 256 1.46** (kept), 4 x 512
1.46, 4 x 128 1.55, 8 x 128 1.47 (8 x 256 does not fit the default 16
MB of VMEM; nothing asks for more). *The first kernel:* PR 38's column
substitution 4.37, of which everything but the inverse 1.50; the same
by rows (the broadcasts on the static ``M``, off the chain) 3.55; **two
chunks side by side in the lanes 4.15**: two broadcasts an update and
half the updates, the XLUs as busy as before; **16-row diagonal blocks
side by side and two merges on the MXU** (``fla``'s ``solve_tril``) 3.32
with the merges' float32 products at ``HIGHEST`` (six passes), 3.04 as
three hand-split bfloat16 passes (``T`` then 1.2e-6 from the
substitution's, of a largest entry of 1); 32-row blocks and one merge
3.32 / 3.26: a third to a half of the inverse's 2.87, because the
blocks' columns still broadcast along the lanes and each ``64 x 64`` merge pays
the MXU's fill for a sixteenth of a tile's work; a lane a chunk with the
rows gathered by strided loads and stores 1.92 (a strided access moves
one sublane an instruction: 8,192 stores a head); by swapped axes
**1.86** (kept). All but the kept form of each kernel are deleted; no
switch, option or environment variable chooses between packings.

**No exponent is ever positive.** ``exp(c_i - c_j)`` is not split into
``exp(c_i) exp(-c_j)``: at a step's log-decay of -1.6 a chunk of 64 sums
to -100 and ``exp(100)`` is past float32. The decay between two rows is
taken relative to a row ``m`` between them, ``exp(c_i - c_m) exp(c_m -
c_j)`` with ``j < m <= i``, both exponents ``<= 0`` (the secondary
chunking of ``fla/ops/kda``, carried down to single rows): the chunk is
halved again and again, and the pairs that a halving separates meet at
its middle row, ``log2(chunk)`` matrix products in all and no
elementwise ``chunk x chunk x dk`` array (:func:`_intra_kernel`). Whatever
underflows is a decay below float32's smallest, which the token
recurrence loses too.

Matrix operands are taken in ``q``'s dtype and accumulated in float32;
the decays, the triangular system and the state are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROWS = 512      # rows of one head a grid step of the first kernel takes
STEP = 1024     # rows of all its heads a grid step of the second takes
LANES = 128     # chunks whose triangular systems are solved side by side
L2_EPS = 1e-6   # under the root of a head's norm


def _precision(dtype):
    """A float32 product at ``highest`` (on a TPU it runs in bfloat16
    passes otherwise); narrower operands as they come."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _unit(x):
    """``x`` [R, d] -> float32, each row over ``sqrt(|row|^2 + eps)``."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _rows_above(x, shift: int):
    """``x`` [R, d] moved down by ``shift`` rows (row ``i`` gets row ``i
    - shift``; negative: up), around the block's end."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, shift % x.shape[0], 0)


def _running_sum(a, chunk: int):
    """``a`` [R, d] float32 -> the sum from each row's chunk's first row
    to the row itself, ``log2(chunk)`` shifted additions."""
    row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0) % chunk
    step = 1
    while step < chunk:
        a = a + jnp.where(row >= step, _rows_above(a, step), 0.0)
        step *= 2
    return a


def _middle_row(c, s: int):
    """``c`` [R, d] -> for each row the row ``m`` that halves its block
    of ``2 s`` rows (``m = block's first + s``): a sublane broadcast
    where the block is at least a tile of 8 rows, else the rows beside
    it, picked by the row's place in its block."""
    r, d = c.shape
    if 2 * s >= 8:
        blocks = c.reshape(r // (2 * s), 2 * s, d)
        return jnp.broadcast_to(blocks[:, s:s + 1, :], blocks.shape
                                ).reshape(r, d)
    place = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0) % (2 * s)
    mid = c
    for at in range(2 * s):
        if at != s:
            mid = jnp.where(place == at, _rows_above(c, at - s), mid)
    return mid


def _solve_by_lanes(x_ref, n: int, chunk: int):
    """``x_ref`` float32 ``[chunk (i), n, chunk (j)]``: ``M`` of ``n``
    chunks, row ``i`` of every chunk together -> in place ``(I +
    M)^-1`` in the same layout. Row ``i`` of all ``n`` matrices is
    transposed to ``[j, n]`` (a lane a chunk) and the substitution goes
    by rows (``(I + M) X = I``: row ``i`` of ``X`` is ``e_i - sum over
    j < i of M[i, j] X[j]``, rows before it final): ``M[i, j]`` of the
    ``n`` chunks is one row of that array, broadcast along the sublanes
    and multiplied into the 8-row tiles of ``X[j]`` that its triangle
    leaves non-zero, a tile's rows ``j`` stacked. Whole registers, no
    broadcast along the lanes, no series in the nilpotent ``M`` whose
    terms could cancel."""
    tall = min(8, chunk)
    place = jax.lax.broadcasted_iota(jnp.int32, (tall, n), 0)
    # tiles[at][j - at * tall]: columns at * tall .. + tall of row j of X,
    # [1, tall, n] (stacked as they are: one equation a sum, not one a row)
    tiles = [[] for _ in range(chunk // tall)]
    for i in range(chunk):
        m_i = x_ref[i].T                                     # [chunk (j), n]
        row = []
        for at in range(i // tall + 1):
            lo = at * tall
            own = (place == i % tall).astype(jnp.float32) \
                if at == i // tall else 0.0
            if i > lo:
                own = own - jnp.sum(m_i[lo:i][:, None, :]
                                    * jnp.concatenate(tiles[at], 0), 0)
            row.append(own)
            tiles[at].append(own[None])
        beyond = chunk - len(row) * tall        # the columns after i's tile
        if beyond:
            row.append(jnp.zeros((beyond, n), jnp.float32))
        x_ref[i] = jnp.concatenate(row, 0).T


def _intra_kernel(q_ref, k_ref, a_ref, beta_ref, t_ref, bqk_ref, x_ref, *,
                  chunk: int, turn: int):
    """One grid step: ``B`` and ``M = diag(beta) A`` (module docstring)
    of the chunks in a block of rows of one head; at the last of every
    ``turn`` steps ``T = (I + M)^-1`` of all the chunks since
    (:func:`_solve_by_lanes`; ``x_ref`` keeps their ``M`` meanwhile,
    ``t_ref`` is the block of all ``turn`` steps' rows). A pair ``i >
    j`` is taken at the level ``s`` of the highest bit in which the two
    rows differ, relative to the row ``m`` that halves their block of
    ``2 s`` rows (``j < m <= i``): one product a level and chunk, of
    rows decayed towards ``m`` from either side."""
    from jax.experimental import pallas as pl

    r, dk = k_ref.shape
    g, dt = r // chunk, q_ref.dtype
    q, k = _unit(q_ref[...]) * dk ** -0.5, _unit(k_ref[...])
    c = _running_sum(a_ref[...].astype(jnp.float32), chunk)
    kq = jnp.concatenate([k.reshape(g, chunk, dk), q.reshape(g, chunk, dk)],
                         1)                                 # [g, 2 C, dk]
    i = jax.lax.broadcasted_iota(jnp.int32, (2 * chunk, chunk), 0) % chunk
    j = jax.lax.broadcasted_iota(jnp.int32, (2 * chunk, chunk), 1)
    is_q = jax.lax.broadcasted_iota(jnp.int32, (2 * chunk, chunk), 0) >= chunk
    # B's diagonal: no decay between a row and itself
    kk = jnp.concatenate([kq[:, :chunk], kq[:, :chunk]], 1)
    both = jnp.where((i == j) & is_q, jnp.sum(kq * kk, -1, keepdims=True),
                     0.0)
    s = 1
    while s < chunk:
        near = jnp.exp(-jnp.abs(c - _middle_row(c, s))       # exponents <= 0
                       ).reshape(g, chunk, dk)
        level = jnp.einsum(
            "gpd,gjd->gpj",
            (kq * jnp.concatenate([near, near], 1)).astype(dt),
            (kq[:, :chunk] * near).astype(dt), precision=_precision(dt),
            preferred_element_type=jnp.float32)
        both += jnp.where((i // (2 * s) == j // (2 * s)) & (i % (2 * s) >= s)
                          & (j % (2 * s) < s), level, 0.0)
        s *= 2
    bqk_ref[...] = both[:, chunk:].reshape(r, chunk).astype(bqk_ref.dtype)
    m = beta_ref[...].astype(jnp.float32).reshape(g, chunk, 1) \
        * both[:, :chunk]
    at = pl.program_id(1) % turn
    # [g, i, j] -> [i, g, j]: row i of this step's chunks, 8 sublanes whole
    x_ref[:, pl.ds(pl.multiple_of(at * g, g), g), :] = jnp.swapaxes(m, 0, 1)

    @pl.when(at == turn - 1)
    def _():
        _solve_by_lanes(x_ref, turn * g, chunk)
        for n in range(turn):
            t_ref[n * r:(n + 1) * r, :] = jnp.swapaxes(
                x_ref[:, n * g:(n + 1) * g, :], 0, 1).reshape(r, chunk
                                                              ).astype(dt)


def _state_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, t_ref, b_ref, o_ref,
                  state_ref, *, chunk: int):
    """One grid step: the chunks in a block of rows of each of its
    heads (the blocks are ``[heads, rows, width]``), in turn, from the
    states the heads' last step left (``state_ref`` float32 ``[heads,
    dv, dk]``: transposed, so that a key channel's decay scales a
    lane); ``t_ref`` holds ``T``, ``b_ref`` ``B``. A chunk's products
    wait on the chunk before it, so the loop takes chunk ``n`` of every
    head before chunk ``n + 1`` of any: the heads' chains are
    independent statements of one block, and one head's product is
    under way while another's result is awaited."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    heads, r, dk = k_ref.shape
    g, dt = r // chunk, q_ref.dtype
    how = dict(precision=_precision(dt), preferred_element_type=jnp.float32)

    def rows(ref):                     # the heads' rows one after another
        return ref[...].reshape(heads * r, ref.shape[-1])

    def cut(x):                        # chunk n of head p at p * g + n
        return x.reshape(heads * g, chunk, x.shape[-1])

    q, kf, v = _unit(rows(q_ref)) * dk ** -0.5, _unit(rows(k_ref)), rows(v_ref)
    c = _running_sum(rows(a_ref).astype(jnp.float32), chunk)
    into = jnp.exp(c)                  # a row's decay since its chunk began
    c = cut(c)
    whole = c[:, chunk - 1:, :]                             # the chunk's sum
    beta = rows(beta_ref).astype(jnp.float32)
    # T diag(beta) [K exp(c) | V]: what the state is multiplied by to give
    # U, and U's part without the state
    wu = jnp.einsum("gij,gjd->gid", cut(rows(t_ref)), jnp.concatenate(
        [cut((beta * kf * into).astype(dt)),
         cut((beta * v.astype(jnp.float32)).astype(dt))], -1), **how)
    # one product a chunk gives both W S0 and (Q exp(c)) S0
    wq = jnp.concatenate([wu[..., :dk].astype(dt),
                          cut((q * into).astype(dt))], 1)
    uv = wu[..., dk:]
    k_out = (cut(kf) * jnp.exp(whole - c)).astype(dt)       # until it ends
    decay, b = jnp.exp(whole), cut(rows(b_ref))
    state = [state_ref[p] for p in range(heads)]
    for n in range(g):
        for p in range(heads):
            at = p * g + n
            seen = jax.lax.dot_general(wq[at], state[p].astype(dt),
                                       (((1,), (1,)), ((), ())), **how)
            u = uv[at] - seen[:chunk]
            o_ref[p, n * chunk:(n + 1) * chunk, :] = seen[chunk:] + jnp.dot(
                b[at], u.astype(dt), **how)
            state[p] = decay[at] * state[p] + jax.lax.dot_general(
                u.astype(dt), k_out[at], (((0,), (0,)), ((), ())), **how)
    for p in range(heads):
        state_ref[p] = state[p]


def _packing(h: int, s: int, chunk: int) -> tuple:
    """How the two kernels' grid steps are filled, read off the shapes
    and nowhere else: ``(rows, turn, heads, rows of a head's state
    step)``. The first kernel takes ``rows`` rows of a head a step
    (whole chunks, at most ``ROWS``, dividing ``S``) and inverts every
    ``turn`` steps, as many as give ``LANES`` chunks a lane each (all
    128 chunks of a head of 8192 tokens at once); the state kernel takes
    the same rows of ``heads`` heads, the largest of 4, 2 and 1 that
    divides ``H``, ``STEP`` rows in all and at most ``ROWS`` of a
    head."""
    def whole_chunks(most):
        return chunk * max(g for g in range(1, max(1, most // chunk) + 1)
                           if s // chunk % g == 0)

    rows = whole_chunks(ROWS)
    g, steps = rows // chunk, s // rows
    turn = max(t for t in range(1, steps + 1)
               if steps % t == 0 and t * g <= max(LANES, g))
    heads = next(p for p in (4, 2, 1) if h % p == 0)
    return rows, turn, heads, whole_chunks(min(ROWS, STEP // heads))


# jitted so that a model's layers of one shape share one trace of the
# unrolled kernels (four KDA layers: 2.5 s of tracing, not 10)
@functools.partial(jax.jit,
                   static_argnames=("chunk", "rows", "turn", "interpret"))
def _matrices(q, k, a, beta, *, chunk: int, rows: int, turn: int,
              interpret: bool):
    """``nns_kda_chunk_intra``: ``T`` and ``B`` of every chunk, ``[H, S,
    chunk]`` in ``q``'s dtype (``rows``, ``turn``: :func:`_packing`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, s, dk = q.shape

    def block(width):
        return pl.BlockSpec((None, rows, width), lambda i, j: (i, j, 0))

    matrix = jax.ShapeDtypeStruct((h, s, chunk), q.dtype)
    return pl.pallas_call(
        functools.partial(_intra_kernel, chunk=chunk, turn=turn),
        out_shape=(matrix, matrix), grid=(h, s // rows),
        in_specs=[block(w) for w in (dk, dk, dk, 1)],
        out_specs=(pl.BlockSpec((None, turn * rows, chunk),
                                lambda i, j: (i, j // turn, 0)),
                   block(chunk)),
        scratch_shapes=[pltpu.VMEM((chunk, turn * rows // chunk, chunk),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="nns_kda_chunk_intra")(q, k, a, beta)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "heads", "rows", "interpret"))
def _states(q, k, v, a, beta, t, b_qk, *, chunk: int, heads: int, rows: int,
            interpret: bool):
    """``nns_kda_chunk_state``: ``o`` float32 ``[H, S, dv]`` (``heads``,
    ``rows``: :func:`_packing`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, s, dk = q.shape
    dv = v.shape[-1]

    def block(width):
        return pl.BlockSpec((heads, rows, width), lambda i, j: (i, j, 0))

    return pl.pallas_call(
        functools.partial(_state_kernel, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((h, s, dv), jnp.float32),
        grid=(h // heads, s // rows),
        in_specs=[block(w) for w in (dk, dk, dv, dk, 1, chunk, chunk)],
        out_specs=block(dv),
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="nns_kda_chunk_state")(
        q, k, v, a, beta, t, b_qk)


def kda_chunked(q, k, v, a, beta, *, chunk: int = 64):
    """``q``, ``k`` [H, S, dk] before their l2 norms, ``v`` [H, S, dv]
    (head-major, as a head's projections write them), ``a`` [H, S, dk]
    float32 log-decays (``<= 0``), ``beta`` [H, S] float32 -> ``o``
    float32 [H, S, dv]: the module docstring's recurrence from a zero
    state on ``k / |k|`` and ``q / |q| * dk^-0.5``, ``chunk`` tokens a
    turn. ``S`` is a multiple of ``chunk``, ``chunk`` a power of two."""
    h, s, _ = q.shape
    if s % chunk or chunk & (chunk - 1):
        raise ValueError(f"kda_chunked: {s} tokens are not a multiple of "
                         f"a chunk of {chunk}, or that is no power of two")
    with jax.named_scope("nns_kda_chunk"):
        a = a.astype(jnp.float32)
        beta = beta.astype(jnp.float32)[..., None]  # a row's, beside its row
        rows, turn, heads, state_rows = _packing(h, s, chunk)
        how = dict(chunk=chunk, interpret=jax.default_backend() != "tpu")
        t, b_qk = _matrices(q, k, a, beta, rows=rows, turn=turn, **how)
        return _states(q, k, v, a, beta, t, b_qk, heads=heads,
                       rows=state_rows, **how)
