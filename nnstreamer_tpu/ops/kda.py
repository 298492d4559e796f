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
system (its inverse ``T`` is formed exactly, by substitution a column at
a time; no series in the nilpotent part, whose terms can cancel
catastrophically) and three products with the state.

Two Pallas TPU kernels under ``jax.named_scope("nns_kda_chunk")``:
``nns_kda_chunk_intra`` makes ``T`` and ``B`` of every chunk (no state
in it: every grid step is independent) and ``nns_kda_chunk_state`` walks
a head's chunks in turn with the state in VMEM: ``S0 -> U, o, S0'``.
Between HBM and the chip go q, k and the decays twice, v and beta once,
the two matrices out and in (half of q's bytes each) and o out; the
decays' running sums, their exponentials, the normalised q and k, ``K
exp(c)``, ``W`` and ``U`` never leave VMEM. Read on the chip at 32 heads
x 8192 tokens of 128, ms a layer (PERF.md, PR 38): the same mathematics
as ``jax.numpy`` with a ``lax.scan`` over the chunks **27.9** (each of
those an array of q's size written and read back: the two matrices
18.2, the inverse 2.6, the running sum 1.7); the two kernels with the
inverse left to XLA between them 21.3 (the inverse alone 18.4 in the
layout that suited the kernels) and 1.8 + 2.2 for the kernels; the
inverse inside the first kernel 6.9, over the tiles of 8 rows that its
triangle leaves non-zero **6.1**. Compiled by Mosaic on a TPU, through
the Pallas interpreter elsewhere (how the CPU tests run it).

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

ROWS = 512      # rows of one head a grid step takes, at most
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


def _intra_kernel(q_ref, k_ref, a_ref, beta_ref, t_ref, bqk_ref, *,
                  chunk: int):
    """One grid step: ``T = (I + diag(beta) A)^-1`` and ``B`` (module
    docstring) of the chunks in a block of rows of one head. A pair ``i
    > j`` is taken at the level ``s`` of the highest bit in which the
    two rows differ, relative to the row ``m`` that halves their block
    of ``2 s`` rows (``j < m <= i``): one product a level and chunk, of
    rows decayed towards ``m`` from either side. The inverse by
    substitution, a column at a time from the last (``X (I + M) = I``:
    column ``j`` of ``X`` is ``e_j - sum over i > j of X[:, i] M[i,
    j]``, so once column ``i`` is final it leaves every column before
    it): ``chunk - 1`` rank-one updates of every chunk's matrix at
    once, exact, no series in the nilpotent ``M`` whose terms could
    cancel."""
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
    eye = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    # the inverse a tile of 8 rows at a time: it is lower triangular, so
    # column ``col`` is zero in the tiles above its own
    tall = min(8, chunk)
    x = [jnp.broadcast_to(eye[lo:lo + tall].astype(jnp.float32),
                          (g, tall, chunk)) for lo in range(0, chunk, tall)]
    for col in range(chunk - 1, 0, -1):
        row = m[:, col:col + 1, :]
        for at in range(col // tall, len(x)):
            x[at] = x[at] - x[at][:, :, col:col + 1] * row
    t_ref[...] = jnp.concatenate(x, 1).reshape(r, chunk).astype(t_ref.dtype)


def _state_kernel(q_ref, k_ref, v_ref, a_ref, beta_ref, t_ref, b_ref, o_ref,
                  state_ref, *, chunk: int):
    """One grid step: the chunks in a block of rows of one head, in
    turn, from the state the head's last step left (``state_ref``
    float32 ``[dv, dk]``: transposed, so that a key channel's decay
    scales a lane); ``t_ref`` holds ``T``, ``b_ref`` ``B``."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    r, dk = k_ref.shape
    g, dt = r // chunk, q_ref.dtype
    q, kf, v = _unit(q_ref[...]) * dk ** -0.5, _unit(k_ref[...]), v_ref[...]
    how = dict(precision=_precision(dt), preferred_element_type=jnp.float32)

    def cut(x):
        return x.reshape(g, chunk, x.shape[-1])

    c = _running_sum(a_ref[...].astype(jnp.float32), chunk)
    into = jnp.exp(c)                  # a row's decay since its chunk began
    c = cut(c)
    whole = c[:, chunk - 1:, :]                             # the chunk's sum
    beta = beta_ref[...].astype(jnp.float32)
    # T diag(beta) [K exp(c) | V]: what the state is multiplied by to give
    # U, and U's part without the state
    wu = jnp.einsum("gij,gjd->gid", cut(t_ref[...]), jnp.concatenate(
        [cut((beta * kf * into).astype(dt)),
         cut((beta * v.astype(jnp.float32)).astype(dt))], -1), **how)
    # one product a chunk gives both W S0 and (Q exp(c)) S0
    wq = jnp.concatenate([wu[..., :dk].astype(dt),
                          cut((q * into).astype(dt))], 1)
    uv = wu[..., dk:]
    k_out = (cut(kf) * jnp.exp(whole - c)).astype(dt)       # until it ends
    decay, b = jnp.exp(whole), cut(b_ref[...])
    state = state_ref[...]
    for n in range(g):
        seen = jax.lax.dot_general(wq[n], state.astype(dt),
                                   (((1,), (1,)), ((), ())), **how)
        u = uv[n] - seen[:chunk]
        o_ref[n * chunk:(n + 1) * chunk, :] = seen[chunk:] + jnp.dot(
            b[n], u.astype(dt), **how)
        state = decay[n] * state + jax.lax.dot_general(
            u.astype(dt), k_out[n], (((0,), (0,)), ((), ())), **how)
    state_ref[...] = state


def kda_chunked(q, k, v, a, beta, *, chunk: int = 64):
    """``q``, ``k`` [H, S, dk] before their l2 norms, ``v`` [H, S, dv]
    (head-major, as a head's projections write them), ``a`` [H, S, dk]
    float32 log-decays (``<= 0``), ``beta`` [H, S] float32 -> ``o``
    float32 [H, S, dv]: the module docstring's recurrence from a zero
    state on ``k / |k|`` and ``q / |q| * dk^-0.5``, ``chunk`` tokens a
    turn. ``S`` is a multiple of ``chunk``, ``chunk`` a power of two."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, s, dk = q.shape
    dv = v.shape[-1]
    if s % chunk or chunk & (chunk - 1):
        raise ValueError(f"kda_chunked: {s} tokens are not a multiple of "
                         f"a chunk of {chunk}, or that is no power of two")
    n, dt = s // chunk, q.dtype
    # the rows a grid step takes: whole chunks, at most ROWS, dividing S
    rows = chunk * max(g for g in range(1, max(1, ROWS // chunk) + 1)
                       if n % g == 0)

    def block(width):
        return pl.BlockSpec((None, rows, width), lambda i, j: (i, j, 0))

    def call(kernel, name, widths, out, order, scratch=()):
        return pl.pallas_call(
            functools.partial(kernel, chunk=chunk), out_shape=out,
            grid=(h, s // rows), in_specs=[block(w) for w in widths],
            out_specs=jax.tree.map(lambda o: block(o.shape[-1]), out),
            scratch_shapes=list(scratch),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", order)),
            interpret=jax.default_backend() != "tpu", name=name)

    with jax.named_scope("nns_kda_chunk"):
        a = a.astype(jnp.float32)
        beta = beta.astype(jnp.float32)[..., None]  # a row's, beside its row
        matrix = jax.ShapeDtypeStruct((h, s, chunk), dt)
        t, b_qk = call(_intra_kernel, "nns_kda_chunk_intra",
                       (dk, dk, dk, 1), (matrix, matrix), "parallel")(
            q, k, a, beta)
        return call(_state_kernel, "nns_kda_chunk_state",
                    (dk, dk, dv, dk, 1, chunk, chunk),
                    jax.ShapeDtypeStruct((h, s, dv), jnp.float32),
                    "arbitrary", [pltpu.VMEM((dv, dk), jnp.float32)])(
            q, k, v, a, beta, t, b_qk)
