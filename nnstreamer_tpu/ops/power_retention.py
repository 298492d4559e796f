"""Chunked gated power retention of degree 2: a normalised linear
attention whose weights are the squared scores (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv 2507.04239; the
``retention`` package's ``power_retention``), a state in and a state
out, so that a document longer than one call is read in constant memory.

For one key/value head with its ``n`` query heads, token ``t`` with
query ``q_t,i`` and key ``k_t`` [d], value ``v_t`` [d] and log-gate
``g_t <= 0`` (``G_t`` their running sum):

    a_ts  = (q_t,i . k_s / d)^2 * exp(G_t - G_s)            s <= t
    o_t,i = sum_s a_ts v_s / (sum_s a_ts + eps)

The degree is even, so every weight is non-negative and the normaliser
is a plain sum. As a recurrence: ``phi(x)`` holds every product ``x_a
x_b`` of two channels once (``x_a^2`` and ``sqrt(2) x_a x_b``, 8256
entries at ``d`` 128), ``phi(q) . phi(k) = (q . k)^2``, and

    S_t = exp(g_t) S_(t-1) + phi(k_t) v_t^T         [phi, d] float32
    Z_t = exp(g_t) Z_(t-1) + k_t k_t^T              [d, d]   float32
    o_t,i = phi(q_t,i)^T S_t / (q_t,i^T Z_t q_t,i + eps)    (q over d)

(the gate decays the state before the token is added: a token sees
itself undecayed). The normaliser's ``phi(q)^T z`` with ``z = sum
phi(k)`` is the quadratic form ``q^T Z q``: ``z``'s 8256 entries are
``Z``'s, so the state keeps the ``d x d`` matrix and the normaliser is
one small product.

**The layout of phi.** Block ``r`` (``0 <= r <= d/2``) of ``phi(x)`` is
``x * roll(x, r)``, ``d`` lanes wide: entry ``a`` is ``x_a x_(a-r)``.
Blocks ``1 .. d/2 - 1`` hold each pair at that circular distance once
and weigh 2 on the key's side, block 0 the squares, block ``d/2`` each
opposite pair twice at weight 1: ``(d/2 + 1) d`` rows, 8320 at ``d``
128 for phi's 8256 (0.8 % more), every block a whole tile, made by one
lane rotation and one multiply a register and never written to memory.
``S`` is kept in that layout, ``[8320, 128]`` float32 a key/value head.

**One Pallas kernel**, ``nns_power_retention``, a grid step a (key/value
head, chunk of ``C`` tokens), the head's state resident in VMEM from its
first chunk to its last (read from HBM and written back once a call):

* the part before the chunk, ``exp(G_t) phi(Q) S`` (``G`` from the
  chunk's start): ``phi`` of a tile of ``ROWS`` queries a ``GROUP`` of
  blocks at a time, bfloat16, times the state's rows in bfloat16,
  accumulated in float32; the decay scales the product's rows;
* the part inside it as the quadratic form, ``(Q K^T)^2 * exp(G_t -
  G_s)`` under the causal mask times ``V``, a tile of queries against
  the keys up to its own;
* ``S <- exp(G_end) S + phi(K)^T (V * exp(G_end - G_s))``: ``K`` is
  transposed once a chunk, ``phi(K)^T``'s blocks are sublane rotations
  of it, and the decay to the chunk's end goes on ``V``'s rows.

Decays only ever as differences inside a chunk, every exponent ``<= 0``
(``exp(G)`` alone overflows: a head may decay by ``exp(-8)`` a step).
Matrix operands in the inputs' dtype (bfloat16 in a model) with float32
accumulation, the state float32. Compiled by Mosaic on a TPU, through
the Pallas interpreter elsewhere (how the CPU tests run it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROWS = 512      # queries a tile of the state product takes
GROUP = 13      # blocks of phi one product takes (65 = 5 x 13 at d 128)
EPS = 1e-6      # beside the normaliser, at the scale of (q . k / d)^2
VMEM_BYTES = 100 * 1024 * 1024     # the state's four buffers are 17 MB


def phi_rows(d: int) -> int:
    """Rows of the state ``S`` a head of ``d`` channels keeps (module
    docstring): ``(d/2 + 1) d``."""
    if d % 2:
        raise ValueError(f"power retention: a head of {d} is odd")
    return (d // 2 + 1) * d


def zero_state(heads: int, d: int):
    """``(S [heads, phi_rows, d], Z [heads, d, d])`` float32 zeros: the
    state before a document."""
    return (jnp.zeros((heads, phi_rows(d), d), jnp.float32),
            jnp.zeros((heads, d, d), jnp.float32))


def _precision(dtype):
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _groups(d: int):
    """phi's blocks, ``GROUP`` at a time (fewer in the last turn)."""
    blocks = list(range(d // 2 + 1))
    return [blocks[i:i + GROUP] for i in range(0, len(blocks), GROUP)]


def _kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, s_in, z_in, o_ref, s_ref,
            z_ref, *, rows: int, scale: float):
    """One grid step: chunk ``n`` of key/value head ``j`` (module
    docstring). ``q_ref`` [n, C, d], ``k_ref`` / ``v_ref`` [C, d],
    ``gc_ref`` [C, 1] and ``gr_ref`` [1, C] the log-gates' running sum
    from the chunk's start, ``s_ref`` [phi, d] and ``z_ref`` [d, d] the
    head's state, the same block through all its chunks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s_in[...]
        z_ref[...] = z_in[...]

    n, c, d = q_ref.shape
    dt = q_ref.dtype
    how = dict(precision=_precision(dt), preferred_element_type=jnp.float32)
    groups = _groups(d)
    kf = k_ref[...].astype(jnp.float32)
    k, v = k_ref[...], v_ref[...]
    gc, gr = gc_ref[...], gr_ref[...]
    z = z_ref[...].astype(dt)

    for t in range(c // rows):
        lo, hi = t * rows, (t + 1) * rows
        into = jnp.exp(gc[lo:hi])                   # [rows, 1], G_t <= 0
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, hi), 0) + lo
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, hi), 1)
        within = jnp.where(
            col <= row, jnp.exp(jnp.minimum(gc[lo:hi] - gr[:, :hi], 0.0)),
            0.0)

        def head(i, _, lo=lo, hi=hi, into=into, within=within):
            qf = q_ref[i, lo:hi, :].astype(jnp.float32) * scale
            qd = qf.astype(dt)
            num = jnp.zeros((rows, d), jnp.float32)
            for blocks in groups:
                phi = jnp.concatenate(
                    [(qf * (pltpu.roll(qf, r, 1) if r else qf)).astype(dt)
                     for r in blocks], 1)
                at = blocks[0] * d
                num += jnp.dot(phi, s_ref[at:at + len(blocks) * d, :]
                               .astype(dt), **how)
            den = jnp.sum(jnp.dot(qd, z, **how) * qf, -1, keepdims=True)
            score = jax.lax.dot_general(qd, k[:hi], (((1,), (1,)), ((), ())),
                                        **how)
            a = score * score * within
            num = num * into + jnp.dot(a.astype(dt), v[:hi], **how)
            den = den * into + jnp.sum(a, -1, keepdims=True)
            o_ref[i, lo:hi, :] = (num / (den + EPS)).astype(o_ref.dtype)
            return _

        jax.lax.fori_loop(0, n, head, 0)

    # G_end, a row of d lanes: the running sum never rises, so its last
    # row is its least (a [1, 1] slice broadcasts in neither direction)
    whole = jnp.min(jnp.broadcast_to(gc, (c, d)), 0, keepdims=True)
    until = jnp.exp(whole - gc)                      # [C, d], <= 1
    vw = (v.astype(jnp.float32) * until).astype(dt)
    kt = kf.T                                        # [d, C]
    decay = jnp.exp(whole)
    for blocks in groups:
        phi_t = jnp.concatenate(
            [((kt * (2.0 if 0 < r < d // 2 else 1.0))
              * (pltpu.roll(kt, r, 0) if r else kt)).astype(dt)
             for r in blocks], 0)
        at = blocks[0] * d
        s_ref[at:at + len(blocks) * d, :] = \
            decay * s_ref[at:at + len(blocks) * d, :] \
            + jnp.dot(phi_t, vw, **how)
    z_ref[...] = decay * z_ref[...] + jnp.dot(
        kt.astype(dt), (kf * until).astype(dt), **how)


# jitted so that a model's layers of one shape share one trace of the
# unrolled kernel
@functools.partial(jax.jit, static_argnames=("chunk", "rows", "interpret"))
def _call(q, k, v, log_g, s, z, *, chunk: int, rows: int, interpret: bool):
    """``nns_power_retention`` over whole chunks: ``(o [Hkv, n, T, d],
    S, Z)``; ``rows`` queries a tile, dividing ``chunk``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv, t, d = k.shape
    n = q.shape[0] // hkv
    # the running sum from each chunk's start, as a column and as a row
    g = jnp.cumsum(log_g.astype(jnp.float32).reshape(hkv, t // chunk, chunk),
                   -1).reshape(hkv, t)

    def block(*shape, index):
        return pl.BlockSpec((None,) + shape, index)

    per_chunk = lambda j, c: (j, c, 0)              # noqa: E731
    per_head = lambda j, c: (j, 0, 0)               # noqa: E731
    state = (block(s.shape[1], d, index=per_head),
             block(d, d, index=per_head))
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, scale=1.0 / d),
        out_shape=(jax.ShapeDtypeStruct((hkv, n, t, d), q.dtype),
                   jax.ShapeDtypeStruct(s.shape, jnp.float32),
                   jax.ShapeDtypeStruct(z.shape, jnp.float32)),
        grid=(hkv, t // chunk),
        in_specs=[pl.BlockSpec((None, n, chunk, d),
                               lambda j, c: (j, 0, c, 0)),
                  block(chunk, d, index=per_chunk),
                  block(chunk, d, index=per_chunk),
                  block(chunk, 1, index=per_chunk),
                  block(1, chunk, index=lambda j, c: (j, 0, c))] + list(state),
        out_specs=(pl.BlockSpec((None, n, chunk, d),
                                lambda j, c: (j, 0, c, 0)),) + state,
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret, name="nns_power_retention")(
        q.reshape(hkv, n, t, d), k, v, g[..., None], g[:, None, :], s, z)


def power_retention(q, k, v, log_g, state=None, *, chunk: int):
    """``q`` [H, T, d] (head-major; query head ``i`` reads key/value
    head ``i // (H / Hkv)``), ``k``, ``v`` [Hkv, T, d], ``log_g`` [Hkv,
    T] float32 (``<= 0``), ``state`` ``(S [Hkv, phi_rows(d), d], Z
    [Hkv, d, d])`` float32 as an earlier call returned it (None: a
    document's start) -> ``(o [H, T, d] in q's dtype, state)``: the
    module docstring's retention over the ``T`` tokens after those the
    state has seen, ``chunk`` tokens a turn. A ``T`` that is no
    multiple of ``chunk`` is filled up with tokens of zero key and
    value and no decay, which leave the state as it was."""
    h, t, d = q.shape
    hkv = k.shape[0]
    if h % hkv:
        raise ValueError("the key/value heads do not divide the heads")
    s, z = zero_state(hkv, d) if state is None else state
    chunk = min(chunk, t)
    pad = -t % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (q, k, v))
        log_g = jnp.pad(log_g, ((0, 0), (0, pad)))
    rows = next(r for r in (ROWS, 256, 128, 64, 32, 16, 8, chunk)
                if chunk % r == 0)
    with jax.named_scope("nns_power_retention"):
        o, s, z = _call(q, k, v, log_g, s, z, chunk=chunk, rows=rows,
                        interpret=jax.default_backend() != "tpu")
    return o.reshape(h, t + pad, d)[:, :t], (s, z)
